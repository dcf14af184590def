"""Versioned snapshot read handles — the ingest/query synchronization.

The publisher publishes the trainer's own device mirror
(``TemporalSampler.mirror``, a copy-on-write :class:`DeviceMirror`):
every sync that changes anything yields a fresh device dict whose
updated arrays are NEW buffers (unchanged arrays are shared), so a
handle pinned by an in-flight query keeps a complete, immutable view of
its version no matter how many deltas land afterwards.

Swap protocol: ``publish`` builds the :class:`SnapshotHandle` off to
the side and installs it with a single reference assignment (atomic
under the GIL).  Readers call :meth:`HandlePublisher.current` once at
batch admission and use only that handle — they never re-read shared
state mid-batch, which is the "queries pin a version" guarantee.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any, Dict, Optional

from repro.core.sampling import DeviceMirror
from repro.core.snapshot import GraphSnapshot


@dataclasses.dataclass(frozen=True)
class SnapshotHandle:
    """One immutable (snapshot version, device arrays, params) triple.

    ``dev`` is the copy-on-write mirror dict for ``version`` — safe to
    sample against from any thread for as long as the handle is held.
    ``params`` are the model parameters the publisher most recently
    associated with this version (jax arrays: immutable)."""
    version: int
    dev: Dict[str, Any]
    params: Any
    t_max: float = 0.0        # newest event timestamp in the snapshot
    n_events: int = 0         # events ingested up to this version
    scan_pages: int = 16
    use_pallas: bool = False


class HandlePublisher:
    """Single-writer publisher of :class:`SnapshotHandle`\\ s.

    ``publish``/``set_params`` are called from the ingest/train thread;
    ``current``/``get`` from any number of query threads.  A small
    version-keyed history is retained so offline parity checks (bench,
    tests) can recompute a forward on the exact handle a response was
    served from, even after newer versions landed.
    """

    def __init__(self, mirror: DeviceMirror, *, scan_pages: int = 16,
                 use_pallas: bool = False, history: int = 8):
        self.mirror = mirror
        self.scan_pages = int(scan_pages)
        self.use_pallas = use_pallas
        self._current: Optional[SnapshotHandle] = None
        self._history: "collections.OrderedDict[int, SnapshotHandle]" = \
            collections.OrderedDict()
        self._hist_cap = int(history)
        self._lock = threading.Lock()   # serializes writers only
        self.publishes = 0

    def publish(self, snap: GraphSnapshot, *, params: Any = None,
                t_max: float = 0.0, n_events: int = 0) -> SnapshotHandle:
        """Sync the mirror to ``snap`` (free where the trainer's
        ``sampler.refresh`` already did) and install a new handle.  The
        old handle (and every handle in history) remains fully
        readable."""
        with self._lock:
            dev = self.mirror.sync(snap)
            prev = self._current
            if params is None and prev is not None:
                params = prev.params
            h = SnapshotHandle(
                version=int(snap.version), dev=dev, params=params,
                t_max=float(t_max), n_events=int(n_events),
                scan_pages=self.scan_pages, use_pallas=self.use_pallas)
            self._install(h)
            self.publishes += 1
            return h

    def set_params(self, params: Any) -> Optional[SnapshotHandle]:
        """Swap in fresh model params without a snapshot change (end of
        a finetune round).  The new handle keeps the current version's
        device arrays — a (version, params) pair stays consistent for
        the lifetime of any pinned handle."""
        with self._lock:
            cur = self._current
            if cur is None:
                return None
            h = dataclasses.replace(cur, params=params)
            self._install(h)
            return h

    def _install(self, h: SnapshotHandle) -> None:
        self._history[h.version] = h          # newest wins per version
        self._history.move_to_end(h.version)
        while len(self._history) > self._hist_cap:
            self._history.popitem(last=False)
        self._current = h                     # atomic swap (GIL)

    def current(self) -> Optional[SnapshotHandle]:
        """The newest handle — ONE read per query batch at admission."""
        return self._current

    def get(self, version: int) -> Optional[SnapshotHandle]:
        """A retained historical handle (parity checks), else None."""
        return self._history.get(int(version))

    def versions(self) -> list:
        """Retained versions, oldest first (warmup sweeps, parity)."""
        return list(self._history.keys())
