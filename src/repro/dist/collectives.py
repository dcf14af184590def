"""Gradient-reduction collectives for data-parallel training.

Three schedules over one contract — sum each gradient leaf across
data-parallel peers:

* ``bucketed_psum``        exact; fuses small leaves into fixed-size
                           buckets so the interconnect sees a few big
                           all-reduces instead of many latency-bound
                           tiny ones.
* ``quantized_psum_grads`` lossy; int8 (or fp16) quantize -> reduce ->
                           dequantize, with error feedback.
* ``topk_psum_grads``      lossy; magnitude top-k sparsification with
                           error feedback (deep-gradient-compression).

Each function accepts either a ``Mesh`` — the call is wrapped in a
shard_map over every mesh axis, arrays being taken as each device's
local values (replicated inputs therefore reduce to n_devices * x; on a
1-device mesh the psum itself is identity, so ``bucketed_psum`` is
exact while the lossy schedules still quantize/sparsify locally) — or
already-bound axis names, for use inside an enclosing shard_map/pmap
body.

Error feedback: the compression residual is returned and must be passed
back as ``err`` on the next call. The transmitted running sum then
tracks the true running sum: per call the quantizer's error is bounded
by ``max|e| / (2 ** (bits - 1) - 1) / 2`` per coordinate (half a
quantization step), and the top-k residual of any coordinate is
retransmitted once it accumulates above the magnitude threshold, so no
coordinate is starved.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any
MeshOrAxes = Union[Mesh, str, Sequence[str]]

_DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024


def grad_payload_bytes(grads: PyTree, mode: str, *, bits: int = 8,
                       frac: float = 0.01) -> int:
    """Per-step, per-worker wire payload of one gradient reduction.

    ``bucketed`` sends every f32 coordinate; ``quantized`` sends bits/8
    bytes per coordinate plus one f32 scale per call; ``topk`` sends
    (int32 index, f32 value) pairs for the ``ceil(frac * n)``
    transmitted coordinates. Used by the distributed trainer/bench to
    compare collective modes without simulating a wire."""
    n = sum(l.size for l in jax.tree_util.tree_leaves(grads))
    if mode == "bucketed":
        return n * 4
    if mode == "quantized":
        return n * bits // 8 + 4
    if mode == "topk":
        k = max(1, min(n, int(round(frac * n))))
        return k * 8
    raise ValueError(f"unknown collective mode {mode!r}")


def _run(fn, leaves: Tuple[jax.Array, ...], mesh_or_axes: MeshOrAxes):
    """Run ``fn(leaves, axes)`` under a shard_map over a Mesh, or inline
    against already-bound axis names."""
    if isinstance(mesh_or_axes, Mesh):
        mesh = mesh_or_axes
        axes = tuple(mesh.axis_names)
        wrapped = jax.shard_map(lambda t: fn(t, axes), mesh=mesh,
                                in_specs=(P(),), out_specs=P(),
                                check_vma=False)
        return wrapped(leaves)
    axes = ((mesh_or_axes,) if isinstance(mesh_or_axes, str)
            else tuple(mesh_or_axes))
    return fn(leaves, axes)


# ---------------------------------------------------------------------------
# Exact: bucketed all-reduce
# ---------------------------------------------------------------------------


def _plan_buckets(leaves: Sequence[jax.Array],
                  bucket_bytes: int) -> List[List[int]]:
    """Greedy fill of leaf indices into <= bucket_bytes buckets, grouped
    by dtype so each bucket concatenates homogeneously. A leaf larger
    than bucket_bytes gets a bucket of its own."""
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    buckets: List[List[int]] = []
    for idxs in by_dtype.values():
        cur: List[int] = []
        cur_bytes = 0
        for i in idxs:
            nbytes = leaves[i].size * jnp.dtype(leaves[i].dtype).itemsize
            if cur and cur_bytes + nbytes > bucket_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
        if cur:
            buckets.append(cur)
    return buckets


def bucketed_psum(grads: PyTree, mesh_or_axes: MeshOrAxes, *,
                  bucket_bytes: int = _DEFAULT_BUCKET_BYTES) -> PyTree:
    """Exact psum of every leaf, fused into fixed-size flat buckets."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    buckets = _plan_buckets(leaves, bucket_bytes)

    def reduce_fn(ls, axes):
        out: List[Optional[jax.Array]] = [None] * len(ls)
        for idx in buckets:
            flat = jnp.concatenate([jnp.ravel(ls[i]) for i in idx])
            red = lax.psum(flat, axes)
            off = 0
            for i in idx:
                n = ls[i].size
                out[i] = red[off:off + n].reshape(ls[i].shape)
                off += n
        return tuple(out)

    reduced = _run(reduce_fn, tuple(leaves), mesh_or_axes)
    return jax.tree_util.tree_unflatten(treedef, reduced)


# ---------------------------------------------------------------------------
# Lossy schedules with error feedback
# ---------------------------------------------------------------------------


def _with_feedback(grads: PyTree, err: Optional[PyTree]
                   ) -> Tuple[List[jax.Array], Any, List]:
    """e = grads + err (f32), flattened; returns (leaves, treedef, shapes)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if err is None:
        e = [jnp.ravel(l).astype(jnp.float32) for l in leaves]
    else:
        err_leaves = jax.tree_util.tree_flatten(err)[0]
        e = [jnp.ravel(l).astype(jnp.float32) + jnp.ravel(r)
             for l, r in zip(leaves, err_leaves)]
    return e, treedef, leaves


def _split_back(flat: jax.Array, like: Sequence[jax.Array], treedef,
                cast: bool) -> PyTree:
    out = []
    off = 0
    for leaf in like:
        n = leaf.size
        piece = flat[off:off + n].reshape(leaf.shape)
        out.append(piece.astype(leaf.dtype) if cast else piece)
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def quantized_psum_grads(grads: PyTree, err: Optional[PyTree],
                         mesh_or_axes: MeshOrAxes, *, bits: int = 8
                         ) -> Tuple[PyTree, PyTree]:
    """Quantize-reduce-dequantize with error feedback.

    bits=8: symmetric per-call scale ``max|e| / 127``; the per-coordinate
    dequantization error is at most half a step, ``max|e| / 254``.
    bits=16: fp16 round-trip (relative error ~2^-11).
    Returns ``(reduced, new_err)``; feed ``new_err`` back on the next
    call so the residual is eventually transmitted.
    """
    if bits not in (8, 16):
        raise ValueError(f"bits must be 8 or 16, got {bits}")
    e_leaves, treedef, leaves = _with_feedback(grads, err)
    if not leaves:
        return grads, grads

    def reduce_fn(es, axes):
        flat = jnp.concatenate(es)
        if bits == 16:
            sent = flat.astype(jnp.float16).astype(jnp.float32)
        else:
            levels = float(2 ** (bits - 1) - 1)
            scale = jnp.maximum(jnp.max(jnp.abs(flat)), 1e-30) / levels
            sent = jnp.round(flat / scale) * scale
        return lax.psum(sent, axes), flat - sent

    red_flat, err_flat = _run(reduce_fn, tuple(e_leaves), mesh_or_axes)
    return (_split_back(red_flat, leaves, treedef, cast=True),
            _split_back(err_flat, leaves, treedef, cast=False))


def topk_psum_grads(grads: PyTree, err: Optional[PyTree],
                    mesh_or_axes: MeshOrAxes, *, frac: float = 0.01
                    ) -> Tuple[PyTree, PyTree]:
    """Magnitude top-k sparsified psum with error feedback.

    Transmits the ``ceil(frac * n)`` largest-magnitude coordinates of
    ``grads + err`` (ties at the threshold may send a few extra); the
    rest accumulate in the returned residual until they clear the
    threshold, so every coordinate is eventually transmitted.
    Returns ``(reduced, new_err)``.
    """
    if not 0.0 < frac <= 1.0:
        raise ValueError(f"frac must be in (0, 1], got {frac}")
    e_leaves, treedef, leaves = _with_feedback(grads, err)
    if not leaves:
        return grads, grads
    total = sum(l.size for l in leaves)
    k = max(1, min(total, int(round(frac * total))))

    def reduce_fn(es, axes):
        flat = jnp.concatenate(es)
        mag = jnp.abs(flat)
        thresh = lax.top_k(mag, k)[0][-1]
        sent = jnp.where(mag >= thresh, flat, 0.0)
        return lax.psum(sent, axes), flat - sent

    red_flat, err_flat = _run(reduce_fn, tuple(e_leaves), mesh_or_axes)
    return (_split_back(red_flat, leaves, treedef, cast=True),
            _split_back(err_flat, leaves, treedef, cast=False))
