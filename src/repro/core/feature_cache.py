"""Vectorized dynamic GPU/TPU feature cache (GNNFlow §4.3).

The paper's design — already vector-shaped, so it maps to JAX directly:

  * one *score* vector per slot; a batch update decrements every score
    (LRU), resets accessed slots to 0 (LRU) or increments them (LFU);
    FIFO keeps a ring pointer;
  * eviction = vectorized top-k over scores;
  * each update replaces at most ``lambda * capacity`` slots (paper's
    anti-thrashing quota, default 0.2);
  * **cache reuse**: state persists across retraining rounds (no
    re-initialization — the paper's Fig. 14 killer);
  * **cache restoration**: snapshot at round start, restore at each epoch
    start so epoch 2+ sees the round's unpolluted cache.

State is a functional pytree; ``FeatureCache`` is the host-side wrapper
owning the jitted ops, hit/miss counters, and the reuse/restore API.
Membership is O(1) via a direct ``slot_of`` map over the id space (node
count or edge count), exactly like the paper's GPU index tensors.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import xfer
from repro.obs.metrics import MetricRegistry

NULL = -1
_NEG = jnp.iinfo(jnp.int32).min // 2


@dataclasses.dataclass
class CacheState:
    slot_of: jnp.ndarray    # (M,) int32: id -> slot | -1
    ids: jnp.ndarray        # (C,) int32: slot -> id | -1
    score: jnp.ndarray      # (C,) int32: policy score
    feats: jnp.ndarray      # (C, D)
    clock: jnp.ndarray      # () int32 (FIFO insertion counter)


jax.tree_util.register_dataclass(
    CacheState, data_fields=["slot_of", "ids", "score", "feats", "clock"],
    meta_fields=[])


def init_cache(capacity: int, dim: int, id_space: int,
               dtype=jnp.float32) -> CacheState:
    return CacheState(
        slot_of=jnp.full((id_space,), NULL, jnp.int32),
        ids=jnp.full((capacity,), NULL, jnp.int32),
        score=jnp.full((capacity,), _NEG, jnp.int32),  # empty = worst
        feats=jnp.zeros((capacity, dim), dtype),
        clock=jnp.zeros((), jnp.int32),
    )


@jax.jit
def cache_lookup(state: CacheState, ids: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ids: (N,) int32 (NULL entries miss). Returns (feats (N,D), hit)."""
    safe = jnp.clip(ids, 0, state.slot_of.shape[0] - 1)
    slot = state.slot_of[safe]
    ok = (ids >= 0) & (slot >= 0)
    slot_c = jnp.clip(slot, 0, state.ids.shape[0] - 1)
    hit = ok & (state.ids[slot_c] == ids)
    feats = jnp.where(hit[:, None], state.feats[slot_c], 0)
    return feats, hit


def _dedup_first(ids: jnp.ndarray) -> jnp.ndarray:
    """Mask selecting the first occurrence of each id (NULLs excluded)."""
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    first = jnp.concatenate([jnp.array([True]),
                             sorted_ids[1:] != sorted_ids[:-1]])
    first = first & (sorted_ids != NULL)
    mask = jnp.zeros_like(first).at[order].set(first)
    return mask


@functools.partial(jax.jit, static_argnames=("policy", "max_replace"))
def cache_update(state: CacheState, ids: jnp.ndarray, hit: jnp.ndarray,
                 miss_feats: jnp.ndarray, *, policy: str,
                 max_replace: int) -> CacheState:
    """Batch access bookkeeping + bounded insertion of missed entries.

    ids: (N,) accessed ids; hit: (N,) from cache_lookup;
    miss_feats: (N, D) feature rows for missed ids (ignored where hit).
    At most `max_replace` (= ceil(lambda*C)) distinct misses are inserted,
    evicting the lowest-score slots (vectorized top-k).
    """
    C = state.ids.shape[0]
    score = state.score

    safe = jnp.clip(ids, 0, state.slot_of.shape[0] - 1)
    slot = jnp.clip(state.slot_of[safe], 0, C - 1)

    # ---- access bookkeeping on hits ----
    if policy == "lru":
        occupied = state.ids != NULL
        score = jnp.where(occupied, score - 1, score)
        score = score.at[slot].max(jnp.where(hit, 0, _NEG),
                                   mode="drop")
    elif policy == "lfu":
        score = score.at[slot].add(jnp.where(hit, 1, 0), mode="drop")
    # fifo: no access bookkeeping

    # ---- choose up to max_replace distinct misses ----
    miss_ids = jnp.where(hit, NULL, ids)
    first = _dedup_first(miss_ids)
    # rank misses by first-occurrence order
    rank = jnp.cumsum(first.astype(jnp.int32)) - 1
    chosen = first & (rank < max_replace)
    n_new = jnp.sum(chosen.astype(jnp.int32))

    # gather the chosen miss rows into a fixed (R,) block
    R = max_replace
    cand_idx = jnp.nonzero(chosen, size=R, fill_value=0)[0]
    cand_valid = jnp.arange(R) < n_new
    new_ids = jnp.where(cand_valid, ids[cand_idx], NULL)
    new_feats = miss_feats[cand_idx]

    # ---- eviction targets ----
    if policy == "fifo":
        # ring buffer: clock counts total insertions; the next R slots
        # after the pointer are replaced (paper: "pointer only moves by
        # the number of entries replaced")
        evict = (state.clock + jnp.arange(R, dtype=jnp.int32)) % C
        evict = jnp.where(cand_valid, evict, C)  # C = no-op sentinel
        clock = state.clock + n_new
    else:
        # vectorized top-k eviction of the R lowest-score slots
        _, evict_slots = jax.lax.top_k(-score, R)
        evict = jnp.where(cand_valid, evict_slots, C)
        clock = state.clock + 1

    evict_c = jnp.clip(evict, 0, C - 1)
    old_ids = jnp.where(evict < C, state.ids[evict_c], NULL)

    # ---- apply: unmap old, map new, write feats/scores ----
    # invalid lanes keep out-of-range indices (C / M) so mode="drop"
    # discards them — clipping them in-range would create duplicate
    # scatter writes that clobber the last slot.
    M = state.slot_of.shape[0]
    slot_of = state.slot_of
    slot_of = slot_of.at[jnp.where(old_ids != NULL, old_ids, M)].set(
        NULL, mode="drop")
    slot_of = slot_of.at[jnp.where(new_ids != NULL, new_ids, M)].set(
        evict_c, mode="drop")

    ids_arr = state.ids.at[evict].set(new_ids, mode="drop")
    feats = state.feats.at[evict].set(new_feats, mode="drop")
    if policy == "lfu":
        new_score = jnp.ones((R,), jnp.int32)
    else:  # lru: most recent; fifo: unused
        new_score = jnp.zeros((R,), jnp.int32)
    score = score.at[evict].set(new_score, mode="drop")

    return CacheState(slot_of=slot_of, ids=ids_arr, score=score,
                      feats=feats, clock=clock)


class FeatureCache:
    """Host wrapper: jitted lookup/update + reuse & restoration (§4.3)."""

    def __init__(self, capacity: int, dim: int, id_space: int, *,
                 policy: str = "lru", lam: float = 0.2,
                 dtype=jnp.float32, use_pallas: bool = False,
                 metrics: Optional[MetricRegistry] = None,
                 name: str = "cache"):
        assert policy in ("lru", "lfu", "fifo")
        self.capacity = int(capacity)
        self.dim = int(dim)
        self.policy = policy
        self.max_replace = max(1, int(np.ceil(lam * capacity)))
        self.state = init_cache(capacity, dim, id_space, dtype)
        self.use_pallas = use_pallas
        # hit/miss accounting lives in a MetricRegistry (shared with the
        # trainer when passed in) — `hits`/`accesses`/`bypassed` remain
        # readable as attributes via the properties below
        self.metrics = metrics if metrics is not None else MetricRegistry()
        self.name = name
        self._c_hits = self.metrics.counter(f"{name}.hits")
        self._c_accesses = self.metrics.counter(f"{name}.accesses")
        self._c_bypassed = self.metrics.counter(f"{name}.bypassed")
        self._c_inserted = self.metrics.counter(f"{name}.inserted")
        self._c_invalidated = self.metrics.counter(f"{name}.invalidated")
        # hit mask of the latest fetch(), aligned with its `ids` arg
        # (callers bucket hits per owner partition from it)
        self.last_hit: Optional[np.ndarray] = None
        self._round_snapshot: Optional[CacheState] = None

    # -- core ops ------------------------------------------------------
    def _lookup_raw(self, ids_j) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if self.use_pallas:
            from repro.kernels.cache_gather.ops import cache_gather_pallas
            return cache_gather_pallas(
                self.state.slot_of, self.state.ids, self.state.feats,
                ids_j)
        return cache_lookup(self.state, ids_j)

    def lookup(self, ids) -> Tuple[jnp.ndarray, jnp.ndarray]:
        ids = jnp.asarray(ids, jnp.int32)
        feats, hit = self._lookup_raw(ids)
        valid = np.asarray(ids) >= 0
        self._c_accesses.add(int(valid.sum()))
        self._c_hits.add(int(np.asarray(hit)[valid].sum()))
        return feats, hit

    def update(self, ids, hit, miss_feats) -> None:
        self.state = cache_update(
            self.state, xfer.h2d(ids, jnp.int32), hit,
            xfer.h2d(miss_feats), policy=self.policy,
            max_replace=self.max_replace)

    def invalidate(self, ids) -> int:
        """Drop the listed ids from the cache (write coherence).

        Ingest calls this for every id it (re)writes: a row cached
        BEFORE its feature landed — e.g. a negative-sampled node read
        while still featureless — would otherwise keep serving its
        stale zeros after the store learned the real value.  Vacated
        slots get the worst policy score so they are refilled first.
        Returns the number of rows dropped."""
        present = self.probe(ids)
        if not present.any():
            return 0
        hot = np.unique(np.asarray(ids, np.int64)[present])
        slot_of = xfer.d2h(self.state.slot_of).copy()
        sids = xfer.d2h(self.state.ids).copy()
        score = xfer.d2h(self.state.score).copy()
        slots = slot_of[hot]
        sids[slots] = NULL
        score[slots] = _NEG
        slot_of[hot] = NULL
        self.state = dataclasses.replace(
            self.state, slot_of=xfer.h2d(slot_of),
            ids=xfer.h2d(sids), score=xfer.h2d(score))
        self._c_invalidated.add(len(hot))
        return len(hot)

    def probe(self, ids) -> np.ndarray:
        """Host-side membership test: True where the id is currently
        cached.  No stats, no policy bookkeeping, no device round trip —
        the prefetcher uses it to skip rows the device cache will hit
        anyway."""
        ids = np.asarray(ids, np.int64)
        slot_of = xfer.d2h(self.state.slot_of)
        sids = xfer.d2h(self.state.ids)
        safe = np.clip(ids, 0, len(slot_of) - 1)
        slot = slot_of[safe]
        ok = (ids >= 0) & (ids < len(slot_of)) & (slot >= 0)
        return ok & (sids[np.clip(slot, 0, len(sids) - 1)] == ids)

    def fetch(self, ids, fetch_missing, cacheable=None) -> jnp.ndarray:
        """lookup -> host-fetch misses via `fetch_missing(ids)` -> update.
        Returns the full (N, D) feature block.

        ``cacheable`` (optional bool mask over ``ids``) makes the cache
        placement-aware: False rows are fetched through but NEVER
        inserted, and the hit/access counters only cover True rows — so
        capacity and hit-rate both measure the rows worth caching (the
        distributed trainers pass the remote-owner mask; locally owned
        rows are a host table lookup already).  Hits remain possible
        only for rows that were cacheable when inserted.

        Request lengths are padded to the next power of two (NULL ids)
        so the jitted lookup/update compile once per bucket, not once
        per batch shape."""
        n = len(ids)
        ids_np = np.asarray(ids, np.int32)
        bucket = max(8, 1 << int(np.ceil(np.log2(max(n, 1)))))
        if bucket != n:
            ids_pad = np.full(bucket, NULL, np.int32)
            ids_pad[:n] = ids_np
        else:
            ids_pad = ids_np
        if cacheable is not None:
            ok = np.zeros(bucket, bool)
            ok[:n] = np.asarray(cacheable, bool)
        else:
            ok = None
        ids_j = xfer.h2d(ids_pad)
        feats, hit = self._lookup_raw(ids_j)
        hit_np = xfer.d2h(hit)
        counted = (ids_pad >= 0) if ok is None else ok
        self._c_accesses.add(int(counted.sum()))
        self._c_hits.add(int(hit_np[counted].sum()))
        if ok is not None:
            self._c_bypassed.add(int(((ids_pad >= 0) & ~ok).sum()))
        need = (~hit_np) & (ids_pad >= 0)
        miss_feats = np.zeros((bucket, self.dim), np.float32)
        if need.any():
            miss_feats[need] = xfer.gather(fetch_missing, ids_pad[need])
        out = jnp.where(hit[:, None], feats, xfer.h2d(miss_feats))
        if ok is None:
            ins_mask = need
            self.update(ids_j, hit, miss_feats)
        else:
            # non-cacheable lanes become NULL so the update never
            # spends a slot (or an eviction) on them
            ins_mask = need & ok
            upd_ids = xfer.h2d(np.where(ok, ids_pad, NULL))
            self.update(upd_ids, hit, miss_feats)
        if ins_mask.any():
            # insertion count computed host-side (distinct misses capped
            # by the anti-thrash quota) — never read back from the device
            self._c_inserted.add(
                min(len(np.unique(ids_pad[ins_mask])), self.max_replace))
        self.last_hit = hit_np[:n]
        return out[:n]

    # -- reuse & restoration (§4.3) -------------------------------------
    def snapshot_round(self) -> None:
        """Call at round start: snapshot for per-epoch restoration."""
        self._round_snapshot = jax.tree.map(lambda x: x.copy(), self.state)

    def restore_epoch(self) -> None:
        """Call at each epoch start: undo intra-round pollution."""
        if self._round_snapshot is not None:
            self.state = jax.tree.map(lambda x: x.copy(),
                                      self._round_snapshot)

    def save_host(self) -> Dict[str, np.ndarray]:
        """Cross-round reuse: export to host memory / disk."""
        return {k: np.asarray(getattr(self.state, k))
                for k in ("slot_of", "ids", "score", "feats", "clock")}

    @classmethod
    def load_host(cls, blob: Dict[str, np.ndarray], **kw) -> "FeatureCache":
        c = cls(capacity=len(blob["ids"]), dim=blob["feats"].shape[1],
                id_space=len(blob["slot_of"]), **kw)
        c.state = CacheState(**{k: jnp.asarray(v) for k, v in blob.items()})
        return c

    # -- stats ----------------------------------------------------------
    @property
    def hits(self) -> int:
        return int(self._c_hits.value)

    @property
    def accesses(self) -> int:
        return int(self._c_accesses.value)

    @property
    def bypassed(self) -> int:
        return int(self._c_bypassed.value)

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.accesses, 1)

    def reset_stats(self) -> None:
        for c in (self._c_hits, self._c_accesses, self._c_bypassed):
            c.reset()

    def contents(self) -> set:
        ids = np.asarray(self.state.ids)
        return set(ids[ids != NULL].tolist())
