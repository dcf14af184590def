"""Static scheduling for distributed sampling (GNNFlow §4.4, Fig. 6).

Policy: when trainer (machine m, local GPU rank r) must sample a target
node owned by machine m', the request is serviced by the GPU with the SAME
local rank r on m'. Every (machine, rank) pair therefore serves exactly
one requester per remote machine per step — deterministic, coordination-
free load balance (the paper measures CV < 0.06 across workers).

WHERE machine m' lives is a transport concern
(``repro.dist.transport``): by default every machine is hosted in this
process and a remote hop is a direct in-process call with byte/latency
accounting; under ``repro.launch.multihost`` each OS process hosts ONE
machine's partition + samplers, serves them to its peers over an RPC
sampling server, and routes hops whose owner is remote through
``transport.sample_hop``.  The schedule, the routing and the measured
balance are identical either way — only the wire is real in the second
case.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.partition import GraphPartition, owner_of
from repro.core.sampling import NULL, SampledLayer, TemporalSampler
from repro.obs import trace
from repro.core.snapshot import (GraphSnapshot, build_snapshot,
                                 refresh_snapshot)


@dataclasses.dataclass
class SamplingLoadStats:
    per_worker_targets: np.ndarray     # (machines, gpus)
    request_bytes: int
    response_bytes: int

    @property
    def cv(self) -> float:
        x = self.per_worker_targets.reshape(-1).astype(np.float64)
        return float(x.std() / x.mean()) if x.mean() else 0.0


class DistributedSamplerSystem:
    """P machines x G gpus; per-machine graph shard + per-rank samplers.

    ``partitions`` are the machines hosted IN THIS PROCESS — all P of
    them in the in-process mode, exactly one in a multihost worker
    (``n_machines`` then names the global machine count and
    ``transport`` carries hops to the other processes' servers).
    Sampler seeds derive from the GLOBAL machine id, so a worker hosting
    only machine m builds bit-identical samplers to the in-process
    system's machine m.
    """

    def __init__(self, partitions: Sequence[GraphPartition], n_gpus: int,
                 fanouts: Sequence[int], policy: str = "recent",
                 window: float = 0.0, scan_pages: int = 16, seed: int = 0,
                 n_machines: Optional[int] = None, transport=None,
                 sample_device=None):
        self.partitions = list(partitions)
        self.n_machines = (n_machines if n_machines is not None
                           else len(partitions))
        self.n_gpus = n_gpus
        self.fanouts = tuple(fanouts)
        self.transport = transport
        # one snapshot per hosted machine, one sampler per (machine,
        # rank): ranks share the machine snapshot object so refresh()
        # can chain SnapshotDeltas into every rank's device mirror.
        # Keyed by GLOBAL machine id (== list index when hosting all).
        self.snaps: Dict[int, GraphSnapshot] = {}
        self.samplers: Dict[int, List[TemporalSampler]] = {}
        self._locks: Dict[int, List[threading.Lock]] = {}
        for part in self.partitions:
            m = part.part_id
            snap = build_snapshot(part.graph)
            self.snaps[m] = snap
            self.samplers[m] = [
                TemporalSampler(snap, fanouts, policy=policy,
                                window=window, scan_pages=scan_pages,
                                seed=seed * 1000 + m * 10 + r,
                                device=sample_device)
                for r in range(n_gpus)]
            self._locks[m] = [threading.Lock() for _ in range(n_gpus)]
        self._load = np.zeros((self.n_machines, n_gpus), np.int64)
        # per-(requesting machine, rank) request sequence: every SPMD
        # process advances its own workers' counters at the same program
        # points, so the (machine, rank, seq, hop) coordinate of any hop
        # is identical in-process and multihost — the request-keyed RNG
        # (TemporalSampler.request_key) rides on it. NOT reset by
        # reset_stats: it tracks program order, not round traffic.
        self._req_seq: Dict[Tuple[int, int], int] = {}
        self.request_bytes = 0
        self.response_bytes = 0
        self.last_refresh_bytes = 0
        self.total_refresh_bytes = 0

    def refresh(self) -> int:
        """Publish per-partition SnapshotDeltas to every rank sampler.

        Each hosted partition keeps ONE chained snapshot:
        ``refresh_snapshot`` mutates it in place and records the delta,
        and every rank sampler mirrors the delta onto its device
        buffers via ``TemporalSampler.refresh`` — O(changed cells) H2D
        per refresh instead of a from-scratch ``build_snapshot``
        (O(graph) re-upload per rank). Version gaps / tau rebuilds fall
        back to a full upload inside the sampler (the PR 2 delta
        protocol). Returns the H2D bytes this refresh moved across all
        hosted ranks (in a multihost worker: this machine's ranks)."""
        total = 0
        for part in self.partitions:
            m = part.part_id
            self.snaps[m] = refresh_snapshot(part.graph, self.snaps[m])
            for r, s in enumerate(self.samplers[m]):
                with self._locks[m][r]:
                    s.refresh(self.snaps[m])
                total += s.mirror.last_refresh_bytes
        self.last_refresh_bytes = total
        self.total_refresh_bytes += total
        return total

    # -- hop service (local call or RPC server entry) ----------------------
    def serve_hop(self, machine: int, rank: int, targets: np.ndarray,
                  times: np.ndarray, pmask: np.ndarray, k: int,
                  req_machine: int = 0, seq: int = 0, hop: int = 0
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                             np.ndarray]:
        """One (already pow2-padded) hop on a hosted sampler.  Called
        directly for locally-owned targets and by the RPC sampling
        server on behalf of remote trainers; the per-sampler lock keeps
        the trainer loop and server threads from interleaving on one
        sampler's device mirror.  (req_machine, seq, hop) is the
        request coordinate: stochastic policies derive their RNG key
        from it (order-independent across serving processes)."""
        worker = self.samplers[machine][rank]
        key = worker.request_key(req_machine, seq, hop)
        with trace.span("sample.serve_hop", machine=machine, rank=rank,
                        n=len(targets)):
            with self._locks[machine][rank]:
                a, b, c, d = worker.sample_hop(targets, times, pmask, k,
                                               key=key)
            return (np.asarray(a), np.asarray(b), np.asarray(c),
                    np.asarray(d))

    def _route_hop(self, trainer_machine: int, rank: int,
                   targets: np.ndarray, times: np.ndarray,
                   tmask: np.ndarray, k: int, seq: int = 0,
                   hop: int = 0):
        """Route one hop's targets to their owners (static schedule)."""
        N = len(targets)
        nbr = np.full((N, k), NULL, np.int32)
        eid = np.full((N, k), NULL, np.int32)
        ts = np.zeros((N, k), np.float32)
        msk = np.zeros((N, k), bool)
        owners = owner_of(np.maximum(targets, 0), self.n_machines)
        for m in range(self.n_machines):
            sel = (owners == m) & tmask & (targets >= 0)
            n_sel = int(sel.sum())
            if not n_sel:
                continue
            # static schedule: remote requests go to the same local rank
            self._load[m, rank] += n_sel
            if m != trainer_machine:
                self.request_bytes += n_sel * 12   # (id, ts)
            # pad each request to a power-of-two length (masked rows) so
            # the per-(shape, fanout) jit cache stays O(log N) even
            # though ownership splits vary batch to batch
            idx = np.nonzero(sel)[0]
            bucket = 1 << (n_sel - 1).bit_length()
            idx_p = np.concatenate(
                [idx, np.full(bucket - n_sel, idx[0], idx.dtype)])
            pmask = np.zeros(bucket, bool)
            pmask[:n_sel] = True
            if m in self.samplers:
                a, b, c, d = self.serve_hop(m, rank, targets[idx_p],
                                            times[idx_p], pmask, k,
                                            req_machine=trainer_machine,
                                            seq=seq, hop=hop)
            else:
                a, b, c, d = self.transport.sample_hop(
                    m, rank, targets[idx_p], times[idx_p], pmask, k,
                    req_machine=trainer_machine, seq=seq, hop=hop)
            nbr[idx] = np.asarray(a)[:n_sel]
            eid[idx] = np.asarray(b)[:n_sel]
            ts[idx] = np.asarray(c)[:n_sel]
            msk[idx] = np.asarray(d)[:n_sel]
            if m != trainer_machine:
                self.response_bytes += n_sel * k * 12
        return nbr, eid, ts, msk

    def sample(self, trainer_machine: int, rank: int, seeds, seed_ts
               ) -> List[SampledLayer]:
        """k-hop distributed sampling from one trainer's perspective."""
        targets = np.asarray(seeds, np.int64)
        times = np.asarray(seed_ts, np.float32)
        tmask = np.ones(len(targets), bool)
        seq = self._req_seq.get((trainer_machine, rank), 0)
        self._req_seq[(trainer_machine, rank)] = seq + 1
        layers: List[SampledLayer] = []
        for hop, k in enumerate(self.fanouts):
            nbr, eid, ts, msk = self._route_hop(
                trainer_machine, rank, targets, times, tmask, k,
                seq=seq, hop=hop)
            layers.append(SampledLayer(
                dst_nodes=targets.astype(np.int32),
                dst_times=times, dst_mask=tmask.copy(),
                nbr_ids=nbr, nbr_eids=eid, nbr_ts=ts, mask=msk))
            targets = nbr.reshape(-1).astype(np.int64)
            times = ts.reshape(-1)
            tmask = msk.reshape(-1)
        return layers

    def load_stats(self) -> SamplingLoadStats:
        return SamplingLoadStats(per_worker_targets=self._load.copy(),
                                 request_bytes=self.request_bytes,
                                 response_bytes=self.response_bytes)

    def reset_stats(self) -> None:
        self._load[:] = 0
        self.request_bytes = 0
        self.response_bytes = 0
