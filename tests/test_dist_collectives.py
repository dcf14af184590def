"""Gradient-compression collectives on a fake 8-device host mesh.

conftest.py forces --xla_force_host_platform_device_count=8 before jax
initializes, so these run in-process (no subprocess hacks). The
1-device identity/error-feedback properties live in
test_train_substrate.py; here we check the multi-device contracts:
bucketed_psum == plain psum exactly, and the lossy schedules meet their
documented error bounds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import AxisType, PartitionSpec as P

from repro.dist.collectives import (bucketed_psum, quantized_psum_grads,
                                    topk_psum_grads)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (fake) devices")


def _mesh8():
    return jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))


def _grads(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(1000,)), jnp.float32),
            "b": {"c": jnp.asarray(rng.normal(size=(37, 5)), jnp.float32),
                  "d": jnp.asarray(rng.normal(size=(3,)), jnp.bfloat16)},
            "e": jnp.asarray(rng.normal(size=(257,)), jnp.float32)}


def test_bucketed_psum_matches_plain_psum_exactly():
    mesh = _mesh8()
    g = _grads()
    got = bucketed_psum(g, mesh, bucket_bytes=2048)
    plain = jax.shard_map(
        lambda t: jax.tree.map(lambda x: lax.psum(x, ("data",)), t),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)(g)
    for k_got, k_plain in zip(jax.tree.leaves(got), jax.tree.leaves(plain)):
        assert k_got.dtype == k_plain.dtype
        np.testing.assert_array_equal(np.asarray(k_got, np.float32),
                                      np.asarray(k_plain, np.float32))


def test_bucketed_psum_distinct_shards_sum():
    """Axes-name form inside an enclosing shard_map: each device holds a
    different gradient; the result must be the cross-device sum."""
    mesh = _mesh8()
    rng = np.random.default_rng(1)
    g_all = jnp.asarray(rng.normal(size=(8, 96)), jnp.float32)

    def body(shard):                      # shard: (1, 96) local slice
        red = bucketed_psum({"w": shard[0]}, ("data",), bucket_bytes=128)
        return red["w"][None]

    out = jax.shard_map(body, mesh=mesh, in_specs=(P("data", None),),
                        out_specs=P("data", None), check_vma=False)(g_all)
    expect = np.asarray(g_all).sum(axis=0)
    for row in np.asarray(out):
        np.testing.assert_allclose(row, expect, rtol=1e-5, atol=1e-5)


def test_quantized_psum_meets_int8_error_bound():
    mesh = _mesh8()
    g = {"w": jnp.asarray(np.random.default_rng(2).normal(size=(512,)),
                          jnp.float32)}
    red, err = quantized_psum_grads(g, None, mesh)
    gw = np.asarray(g["w"])
    # replicated input: psum == 8 * dequantized local value
    deq = np.asarray(red["w"]) / 8.0
    bound = np.max(np.abs(gw)) / 254.0     # half a step of max|e|/127
    assert np.max(np.abs(deq - gw)) <= bound * (1 + 1e-5)
    # residual consistency: transmitted + residual == input
    np.testing.assert_allclose(deq + np.asarray(err["w"]), gw,
                               rtol=1e-6, atol=1e-6)


def test_quantized_psum_fp16_mode():
    mesh = _mesh8()
    g = {"w": jnp.asarray(np.random.default_rng(3).normal(size=(256,)),
                          jnp.float32)}
    red, _ = quantized_psum_grads(g, None, mesh, bits=16)
    deq = np.asarray(red["w"]) / 8.0
    gw = np.asarray(g["w"])
    # fp16 round-trip: relative error ~2^-11 per coordinate
    np.testing.assert_allclose(deq, gw, rtol=2 ** -10, atol=2 ** -16)


def test_topk_psum_sparsity_and_exactness_on_sent_coords():
    mesh = _mesh8()
    n, frac = 640, 0.1
    gw = np.random.default_rng(4).normal(size=(n,)).astype(np.float32)
    g = {"w": jnp.asarray(gw)}
    red, err = topk_psum_grads(g, None, mesh, frac=frac)
    deq = np.asarray(red["w"]) / 8.0
    sent = deq != 0.0
    k = int(round(frac * n))
    assert k <= sent.sum() <= k + 4        # ties may add a few
    # sent coordinates are transmitted (up to all-reduce summation
    # order); the rest land in err exactly (local arithmetic)
    np.testing.assert_allclose(deq[sent], gw[sent], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(err["w"])[~sent], gw[~sent])
    assert np.all(np.asarray(err["w"])[sent] == 0.0)
    # and the k sent ones are the largest magnitudes
    assert np.min(np.abs(gw[sent])) >= np.max(np.abs(gw[~sent]))
