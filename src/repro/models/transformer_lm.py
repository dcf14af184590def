"""LM backbones for every assigned architecture family.

One parameter tree + three entry points per config:
  * ``forward_hidden``  — train/prefill full-sequence forward (scan over
    layers, optional per-layer remat, optional KV/state collection for
    prefill).
  * ``decode_forward``  — single-token step against a decode state
    (KV caches for attention layers, conv+SSM states for mamba layers).
  * ``init_lm`` / ``init_decode_state``.

Families:
  dense/moe/vlm/audio — (attn + mlp|moe) blocks, stacked with lax.scan.
  ssm (falcon-mamba)  — pure mamba1 blocks.
  hybrid (zamba2)     — scan over "superlayers": (attn_every - 1) mamba2
    blocks followed by ONE weight-tied shared attention+MLP block (the
    zamba2 shared-block design); the shared block's KV cache is per
    *application* (n_super entries), its weights a single set.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.dist.sharding import constrain, gather_fsdp
from repro.models import mamba as M
from repro.models.layers import (apply_rope, blocked_attention,
                                 decode_attention, dense_init,
                                 direct_attention, embed_init, mlp_apply,
                                 mlp_param_shapes, rms_norm)
from repro.models.moe import moe_apply, moe_init

PyTree = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_attn(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d, Hq, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, Hq * Dh), dtype),
        "wk": dense_init(ks[1], (d, Hkv * Dh), dtype),
        "wv": dense_init(ks[2], (d, Hkv * Dh), dtype),
        "wo": dense_init(ks[3], (Hq * Dh, d), dtype,
                         scale=(Hq * Dh) ** -0.5 / math.sqrt(
                             2 * cfg.n_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((Dh,), dtype)
        p["k_norm"] = jnp.ones((Dh,), dtype)
    return p


def _init_mlp(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    shapes = mlp_param_shapes(cfg.d_model, cfg.d_ff, cfg.act)
    ks = jax.random.split(key, len(shapes))
    return {n: dense_init(k, s, dtype)
            for (n, s), k in zip(sorted(shapes.items()), ks)}


def _init_block(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    ka, kf = jax.random.split(key)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": _init_attn(ka, cfg, dtype),
    }
    if cfg.moe is not None:
        p["moe"] = moe_init(kf, cfg.moe, cfg.d_model, cfg.act, dtype)
    else:
        p["mlp"] = _init_mlp(kf, cfg, dtype)
    return p


def _init_mamba_layer(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    version = cfg.ssm.version
    init = M.mamba1_init if version == 1 else M.mamba2_init
    return {
        "ln": jnp.ones((cfg.d_model,), dtype),
        f"mamba{version}": init(key, cfg.ssm, cfg.d_model, dtype),
    }


def init_lm(cfg: ArchConfig, key: jax.Array, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    if cfg.input_kind == "tokens":
        params["embed"] = embed_init(ks[0], (cfg.vocab, cfg.d_model), dtype)
    else:  # frames: frontend stub; learned input proj + mask embedding
        params["in_proj"] = dense_init(ks[0], (cfg.d_model, cfg.d_model),
                                       dtype)
        params["mask_emb"] = embed_init(ks[6], (cfg.d_model,), dtype)

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        layer_keys = jax.random.split(ks[1], cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: _init_block(k, cfg, dtype))(layer_keys)
    elif cfg.family == "ssm":
        layer_keys = jax.random.split(ks[1], cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: _init_mamba_layer(k, cfg, dtype))(layer_keys)
    elif cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        n_inner = cfg.attn_every - 1
        sl_keys = jax.random.split(ks[1], n_super * n_inner).reshape(
            n_super, n_inner, 2)
        params["superlayers"] = jax.vmap(jax.vmap(
            lambda k: _init_mamba_layer(k, cfg, dtype)))(sl_keys)
        params["shared"] = _init_block(ks[2], cfg, dtype)
    else:
        raise ValueError(cfg.family)

    params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[3], (cfg.d_model, cfg.vocab),
                                       dtype, scale=cfg.d_model ** -0.5)
    return params


# ---------------------------------------------------------------------------
# Attention (full-sequence and decode-step)
# ---------------------------------------------------------------------------


def _qkv(p: dict, x: jnp.ndarray, cfg: ArchConfig, positions: jnp.ndarray):
    B, S, _ = x.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = (x @ p["wq"]).reshape(B, S, Hq, Dh)
    k = (x @ p["wk"]).reshape(B, S, Hkv, Dh)
    v = (x @ p["wv"]).reshape(B, S, Hkv, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


_CP_SCORE_BYTES_LIMIT = 5e9  # per-chip f32 score block budget


def _cp_attention_shard_map(q, k, v, *, causal: bool,
                            blocked: bool = False) -> jnp.ndarray:
    """Context-parallel attention as an explicit shard_map (§Perf A1/P1).

    q/k/v arrive seq-sharded over the 'seq_act' axis. Each device
    all-gathers K/V (tiled ring) and computes its query shard's attention
    locally; the all-gather's transpose is a reduce-scatter of dK/dV —
    under pure GSPMD constraints the backward instead summed full-dx
    activations (measured 2.6 GB f32 x2/layer on qwen3-14b train_4k).

    `blocked=True` runs the memory-safe online-softmax scan INSIDE the
    shard (sequence-parallel 32k prefill, §Perf P1: local score blocks
    instead of (S_loc x S) f32 tensors).
    """
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import active_mesh, axis_for

    mesh = active_mesh()
    dp_ax = axis_for("batch")
    sp_ax = axis_for("seq_act")
    sp_name = sp_ax if isinstance(sp_ax, str) else sp_ax[0]

    def body(q_l, k_l, v_l):
        # (B_loc, S_loc, H, D); gather the full K/V sequence
        k_f = lax.all_gather(k_l, sp_name, axis=1, tiled=True)
        v_f = lax.all_gather(v_l, sp_name, axis=1, tiled=True)
        offset = lax.axis_index(sp_name) * q_l.shape[1]
        if blocked:
            B = q_l.shape[0]
            return blocked_attention(
                q_l, k_f, v_f, causal=causal,
                q_offset=jnp.full((B,), offset, jnp.int32))
        return direct_attention(q_l, k_f, v_f, causal=causal,
                                q_offset=offset)

    spec = P(dp_ax, sp_ax, None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attn_full(p: dict, x: jnp.ndarray, cfg: ArchConfig,
              positions: jnp.ndarray,
              q_chunk: int = 512, kv_chunk: int = 1024):
    """x: (B, S, d) (already normed). Returns (out, (k, v)).

    Path selection: when the sequence axis is sharded ('seq_act' rule,
    context parallelism) and the per-chip score block fits, use
    direct_attention with q S-sharded and K/V all-gathered — attention
    then runs without internal collectives. Otherwise fall back to the
    memory-safe blocked online-softmax scan (e.g. 32k prefill).
    """
    from repro.dist.sharding import axis_for, axis_size_of

    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    seq_ax = axis_for("seq_act")
    if seq_ax is not None and S % max(axis_size_of("seq_act"), 1) == 0:
        dp = max(axis_size_of("batch"), 1)
        sp = max(axis_size_of("seq_act"), 1)
        score_bytes = (B / dp) * cfg.n_heads * (S / sp) * S * 4.0
        # small score block: single-shot local attention; big (32k
        # prefill): blocked online-softmax inside the shard (§Perf P1)
        o = _cp_attention_shard_map(
            q, k, v, causal=cfg.causal,
            blocked=score_bytes > _CP_SCORE_BYTES_LIMIT)
    else:
        o = blocked_attention(q, k, v, causal=cfg.causal,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = o.reshape(B, S, -1) @ p["wo"]
    return out, (k, v)


def attn_decode(p: dict, x_t: jnp.ndarray, k_cache: jnp.ndarray,
                v_cache: jnp.ndarray, pos: jnp.ndarray, cfg: ArchConfig):
    """x_t: (B, 1, d) normed; caches (B, S, Hkv, Dh); pos: (B,).

    Cache write uses a shared write index (pos[0]) via dynamic_update_slice:
    a per-row scatter would force GSPMD to all-gather the cache (measured:
    17 GB/step on yi-6b decode_32k); batched decode steps share the step
    index in this serving design. Per-row positions still mask attention.
    """
    B = x_t.shape[0]
    q, k_new, v_new = _qkv(p, x_t, cfg, pos[:, None])
    k_cache = lax.dynamic_update_slice_in_dim(k_cache, k_new, pos[0], axis=1)
    v_cache = lax.dynamic_update_slice_in_dim(v_cache, v_new, pos[0], axis=1)
    o = decode_attention(q, k_cache, v_cache, valid_len=pos + 1)
    out = o.reshape(B, 1, -1) @ p["wo"]
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def _block_apply(cfg: ArchConfig, p: dict, x: jnp.ndarray,
                 positions: jnp.ndarray):
    h, kv = attn_full(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), cfg,
                      positions)
    x = x + h
    hn = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        ff, aux = moe_apply(p["moe"], hn, cfg.moe, cfg.act)
    else:
        ff, aux = mlp_apply(hn, p["mlp"], cfg.act), {}
    x = x + ff
    x = constrain(x, "batch", "seq_act", "embed_act")
    return x, aux, kv


def forward_hidden(cfg: ArchConfig, params: dict, x: jnp.ndarray,
                   positions: jnp.ndarray, collect_state: bool = False
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], PyTree]:
    """x: (B, S, d) embedded input. Returns (hidden, aux, state|None).

    state (when collect_state): family-dependent prefill decode-state
    ingredients — attention KV stacks and/or mamba states.
    """
    zero = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        def body(carry, lp):
            xc, lb, dr = carry
            lp = gather_fsdp(lp)
            xc, aux, kv = _block_apply(cfg, lp, xc, positions)
            lb = lb + aux.get("moe_lb_loss", zero)
            dr = dr + aux.get("moe_drop_frac", zero)
            return (xc, lb, dr), (kv if collect_state else None)

        if cfg.remat != "none":
            body = jax.checkpoint(body)
        (x, lb, dr), kvs = lax.scan(body, (x, zero, zero), params["layers"])
        aux = {"moe_lb_loss": lb / cfg.n_layers,
               "moe_drop_frac": dr / cfg.n_layers}
        state = {"k": kvs[0], "v": kvs[1]} if collect_state else None
        return x, aux, state

    if cfg.family == "ssm":
        def body(xc, lp):
            lp = gather_fsdp(lp)
            out = M.mamba1_forward(
                lp["mamba1"], rms_norm(xc, lp["ln"], cfg.norm_eps),
                cfg.ssm, return_state=collect_state)
            if collect_state:
                y, st = out
            else:
                y, st = out, None
            return xc + y, st

        if cfg.remat != "none":
            body = jax.checkpoint(body)
        x, states = lax.scan(body, x, params["layers"])
        return x, {}, ({"mamba": states} if collect_state else None)

    if cfg.family == "hybrid":
        shared = params["shared"]

        def super_body(xc, slp):
            slp = gather_fsdp(slp)

            def inner(xi, lp):
                out = M.mamba2_forward(
                    lp["mamba2"], rms_norm(xi, lp["ln"], cfg.norm_eps),
                    cfg.ssm, return_state=collect_state)
                if collect_state:
                    y, st = out
                else:
                    y, st = out, None
                return xi + y, st

            xc, sts = lax.scan(inner, xc, slp)
            h, kv = attn_full(shared["attn"],
                              rms_norm(xc, shared["ln1"], cfg.norm_eps),
                              cfg, positions)
            xc = xc + h
            xc = xc + mlp_apply(
                rms_norm(xc, shared["ln2"], cfg.norm_eps), shared["mlp"],
                cfg.act)
            xc = constrain(xc, "batch", "seq_act", "embed_act")
            return xc, (sts, kv) if collect_state else None

        if cfg.remat != "none":
            super_body = jax.checkpoint(super_body)
        x, ys = lax.scan(super_body, x, params["superlayers"])
        if collect_state:
            sts, kvs = ys
            state = {"mamba": sts, "k": kvs[0], "v": kvs[1]}
        else:
            state = None
        return x, {}, state

    raise ValueError(cfg.family)


def embed_input(cfg: ArchConfig, params: dict, batch: Dict[str, jnp.ndarray],
                dtype=jnp.bfloat16) -> jnp.ndarray:
    if cfg.input_kind == "tokens":
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    else:
        frames = batch["frames"].astype(dtype)
        x = frames @ params["in_proj"]
        if "mask" in batch:  # masked-prediction training (HuBERT)
            x = jnp.where(batch["mask"][..., None], params["mask_emb"], x)
    return constrain(x.astype(dtype), "batch", "seq_act", "embed_act")


def unembed_weight(cfg: ArchConfig, params: dict) -> jnp.ndarray:
    if cfg.tie_embeddings or "lm_head" not in params:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# Decode state + single-token forward
# ---------------------------------------------------------------------------


def init_decode_state(cfg: ArchConfig, batch: int, max_seq: int,
                      dtype=jnp.bfloat16) -> dict:
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim_
    state: Dict[str, Any] = {"pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        L = cfg.n_layers
        state["k"] = jnp.zeros((L, batch, max_seq, Hkv, Dh), dtype)
        state["v"] = jnp.zeros((L, batch, max_seq, Hkv, Dh), dtype)
    elif cfg.family == "ssm":
        L = cfg.n_layers
        init = M.mamba1_init_state(cfg.ssm, cfg.d_model, batch, dtype)
        state["mamba"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape), init)
    elif cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_every
        n_inner = cfg.attn_every - 1
        init = M.mamba2_init_state(cfg.ssm, cfg.d_model, batch, dtype)
        state["mamba"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_super, n_inner) + a.shape),
            init)
        state["k"] = jnp.zeros((n_super, batch, max_seq, Hkv, Dh), dtype)
        state["v"] = jnp.zeros((n_super, batch, max_seq, Hkv, Dh), dtype)
    return state


def decode_forward(cfg: ArchConfig, params: dict, x: jnp.ndarray,
                   state: dict) -> Tuple[jnp.ndarray, dict]:
    """x: (B, 1, d) embedded token. Returns (hidden (B, 1, d), new state)."""
    pos = state["pos"]
    new_state = dict(state)

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        def body(xc, xs):
            lp, kc, vc = xs
            h, kc, vc = attn_decode(
                lp["attn"], rms_norm(xc, lp["ln1"], cfg.norm_eps), kc, vc,
                pos, cfg)
            xc = xc + h
            hn = rms_norm(xc, lp["ln2"], cfg.norm_eps)
            if cfg.moe is not None:
                ff, _ = moe_apply(lp["moe"], hn, cfg.moe, cfg.act)
            else:
                ff = mlp_apply(hn, lp["mlp"], cfg.act)
            return xc + ff, (kc, vc)

        x, (ks, vs) = lax.scan(body, x, (params["layers"], state["k"],
                                         state["v"]))
        new_state.update(k=ks, v=vs)

    elif cfg.family == "ssm":
        def body(xc, xs):
            lp, st = xs
            y, st = M.mamba1_decode_step(
                lp["mamba1"],
                rms_norm(xc[:, 0], lp["ln"], cfg.norm_eps), st, cfg.ssm)
            return xc + y[:, None], st

        x, sts = lax.scan(body, x, (params["layers"], state["mamba"]))
        new_state.update(mamba=sts)

    elif cfg.family == "hybrid":
        shared = params["shared"]

        def super_body(xc, xs):
            slp, msts, kc, vc = xs

            def inner(xi, ys):
                lp, st = ys
                y, st = M.mamba2_decode_step(
                    lp["mamba2"],
                    rms_norm(xi[:, 0], lp["ln"], cfg.norm_eps), st, cfg.ssm)
                return xi + y[:, None], st

            xc, msts = lax.scan(inner, xc, (slp, msts))
            h, kc, vc = attn_decode(
                shared["attn"], rms_norm(xc, shared["ln1"], cfg.norm_eps),
                kc, vc, pos, cfg)
            xc = xc + h
            xc = xc + mlp_apply(
                rms_norm(xc, shared["ln2"], cfg.norm_eps), shared["mlp"],
                cfg.act)
            return xc, (msts, kc, vc)

        x, (msts, ks, vs) = lax.scan(
            super_body, x,
            (params["superlayers"], state["mamba"], state["k"],
             state["v"]))
        new_state.update(mamba=msts, k=ks, v=vs)
    else:
        raise ValueError(cfg.family)

    new_state["pos"] = pos + 1
    return x, new_state
