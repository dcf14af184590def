"""Assemble sampled neighborhoods + fetched features into jit-ready
batches ("MFG"s, message-flow graphs, following TGL's terminology).

This is the paper's *feature fetching* phase: node/edge features come
through the device FeatureCache (GNNFlow §4.3) backed by a (possibly
owner-sharded, cross-process) ``StateService``
(``repro.core.feature_store``); TGN node memories are always fetched
fresh (they mutate every batch — caching them would serve stale state).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sampling import SampledLayer
from repro.obs import trace, xfer


def _record_hops(layers: List[SampledLayer]) -> None:
    """With tracing on, a ``sampler.hops`` span whose args are the fused
    dispatch's counts per hop: live ``targets``, their ``candidates``
    summed, and the ``deep`` targets, whose candidates reach past their
    ``scan_pages`` newest pages. Read after the layers' first read has
    waited for the dispatch, so the copy waits for nothing."""
    if not trace.enabled() or any(l.stats is None for l in layers):
        return
    with trace.span("sampler.hops") as sp:
        s = np.asarray(jax.device_get([l.stats for l in layers]))
        sp.set(targets=s[:, 0].tolist(), candidates=s[:, 1].tolist(),
               deep=s[:, 2].tolist())


def assemble(layers: List[SampledLayer],
             fetch_node: Callable[[np.ndarray], np.ndarray],
             fetch_edge: Callable[[np.ndarray], np.ndarray],
             fetch_memory: Optional[Callable[[np.ndarray], np.ndarray]]
             = None) -> List[Dict[str, jnp.ndarray]]:
    """Returns hops[l] dicts for repro.models.gnn.gnn_embed."""
    hops = []
    for l, layer in enumerate(layers):
        dst_ids = xfer.d2h(layer.dst_nodes, np.int64)
        if l == 0:
            _record_hops(layers)
        nbr_ids = xfer.d2h(layer.nbr_ids, np.int64)
        eids = xfer.d2h(layer.nbr_eids, np.int64)
        N, K = nbr_ids.shape

        dst_feat = xfer.d2h(fetch_node(dst_ids))
        nbr_feat = xfer.d2h(fetch_node(nbr_ids.reshape(-1))) \
            .reshape(N, K, -1)
        edge_feat = xfer.d2h(fetch_edge(eids.reshape(-1))) \
            .reshape(N, K, -1)
        if fetch_memory is not None:
            dst_mem = xfer.d2h(fetch_memory(dst_ids))
            nbr_mem = xfer.d2h(
                fetch_memory(nbr_ids.reshape(-1))).reshape(N, K, -1)
            dst_feat = np.concatenate([dst_feat, dst_mem], axis=-1)
            nbr_feat = np.concatenate([nbr_feat, nbr_mem], axis=-1)

        mask = xfer.d2h(layer.mask)
        dt = xfer.d2h(layer.dst_times)[:, None] - xfer.d2h(layer.nbr_ts)
        dt = np.where(mask, np.maximum(dt, 0.0), 0.0)

        hops.append({
            "dst_feat": xfer.h2d(dst_feat, jnp.float32),
            "nbr_feat": xfer.h2d(nbr_feat, jnp.float32),
            "edge_feat": xfer.h2d(edge_feat, jnp.float32),
            "dt": xfer.h2d(dt, jnp.float32),
            "mask": xfer.h2d(mask),
        })
    return hops
