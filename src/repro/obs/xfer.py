"""Host<->device copies and host row reads, spanned while tracing is on.

Each helper does exactly what its plain form does (``jnp.asarray``,
``np.asarray``, a call) and, only while tracing is on, records a span
over it: ``xfer.h2d`` and ``xfer.d2h`` with the ``bytes`` the copy
moved, ``state.gather`` with the ``rows`` read. A ``d2h`` span also
covers the wait for the device to produce the array, so a device wait is
measured at the read the program makes anyway. With tracing off the
added cost is one flag test: nothing is counted, timed or allocated.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace


def h2d(x: Any, dtype: Any = None) -> jax.Array:
    """``jnp.asarray(x, dtype)``; an ``xfer.h2d`` span over the upload of a
    host array (an array already on the device moves nothing)."""
    if not trace._enabled or isinstance(x, jax.Array):
        return jnp.asarray(x, dtype)
    with trace.span("xfer.h2d") as sp:
        out = jnp.asarray(x, dtype)
        sp.set(bytes=out.nbytes)
    return out


def d2h(x: Any, dtype: Any = None) -> np.ndarray:
    """``np.asarray(x, dtype)``; an ``xfer.d2h`` span over the wait for and
    the copy of a device array (a host array moves nothing)."""
    if not trace._enabled or not isinstance(x, jax.Array):
        return np.asarray(x, dtype)
    with trace.span("xfer.d2h", bytes=x.nbytes):
        return np.asarray(x, dtype)


def gather(read: Callable[[Any], Any], ids: Any) -> Any:
    """``read(ids)``, a read of host rows; a ``state.gather`` span over it."""
    if not trace._enabled:
        return read(ids)
    with trace.span("state.gather", rows=len(ids)):
        return read(ids)
