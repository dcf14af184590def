"""Pallas TPU kernel: forward flash attention (GQA, causal), the 32k
prefill hotspot (EXPERIMENTS §Perf P1).

Why: the pure-JAX blocked path still round-trips every (q-block,
kv-block) score tile through HBM — at 32k that is B*H*S^2 * 4 bytes per
layer (~343 GB/chip/layer on qwen3-14b prefill), the dominant memory
term of all seven prefill cells. This kernel keeps the running softmax
state (m, l, acc) in VMEM scratch across the KV grid dimension, so HBM
traffic collapses to the q/k/v reads and the output write.

Sequence parallelism cannot fix this (per-chip score traffic is
(tokens/chips) * S no matter which way tokens are split — §Perf P1);
only VMEM residency can.

Grid: (B, Hq, Sq/QB, Skv/KB) with the KV axis as the sequential minor
dim (scratch persists across it). Causal skipping: blocks entirely above
the diagonal contribute nothing and are skipped via pl.when (on TPU this
prunes the compute; the DMA still runs — static block shapes).
Forward-only: serving path (prefill/decode need no backward); training
uses the pure-JAX paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            qb: int, kb: int, n_kv: int, causal: bool, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_start = qi * qb
    k_start = ki * kb
    # causal: skip blocks strictly above the diagonal
    run = (not causal) or (k_start <= q_start + qb - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0, :, 0, :]                  # (QB, D)
        k = k_ref[0, :, 0, :]                  # (KB, D)
        v = v_ref[0, :, 0, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (QB, KB)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (qb, kb), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (qb, kb), 1)
            s = jnp.where(kpos > qpos, NEG_INF, s)
        m_prev = m_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_s[:, 0] = l_s[:, 0] * corr + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (QB, D)
        acc_s[...] = acc_s[...] * corr[:, None] + pv
        m_s[:, 0] = m_new

    @pl.when(ki == n_kv - 1)
    def _fin():
        denom = jnp.maximum(l_s[:, 0], 1e-30)
        o_ref[0, :, 0, :] = (acc_s[...] / denom[:, None]
                             ).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, qb: int = 128,
                           kb: int = 128):
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) with Hq % Hkv == 0.
    Sq % qb == 0 and Skv % kb == 0 (ops.py pads)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    qb = min(qb, Sq)
    kb = min(kb, Skv)
    n_q, n_kv = Sq // qb, Skv // kb
    grid = (B, Hq, n_q, n_kv)

    q_spec = pl.BlockSpec((1, qb, 1, D), lambda b, h, qi, ki: (b, qi, h, 0))
    kv_spec = pl.BlockSpec((1, kb, 1, D),
                           lambda b, h, qi, ki: (b, ki, h // G, 0))
    o_spec = pl.BlockSpec((1, qb, 1, D), lambda b, h, qi, ki: (b, qi, h, 0))

    fn = pallas_call(
        functools.partial(_kernel, qb=qb, kb=kb, n_kv=n_kv, causal=causal,
                          scale=D ** -0.5),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, Hq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, 1), jnp.float32),   # running max
            pltpu.VMEM((qb, 1), jnp.float32),   # running sum
            pltpu.VMEM((qb, D), jnp.float32),   # output accumulator
        ],
    )
    return fn(q, k, v)
