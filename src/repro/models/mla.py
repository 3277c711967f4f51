"""Multi-head Latent Attention (DeepSeek-V2/V3, arXiv:2412.19437).

Q and KV are projected through low-rank latents; only the compressed KV latent
(kv_lora_rank + qk_rope_dim per token — 576 floats for V3, independent of the
128 heads) is cached.  Decode uses the *absorbed-weights* form: W_uk is folded
into the query and W_uv into the output so attention runs directly against the
latent cache — the production trick that makes MLA decode memory-lean.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import sharding as sh
from .layers import (append_rows, dense_init, layer_view, layer_write,
                     rmsnorm, rope)


class MLACache(NamedTuple):
    ckv: jax.Array           # (B, S_max, kv_lora_rank)
    krope: jax.Array         # (B, S_max, qk_rope_dim)
    length: jax.Array        # (B,)


def mla_params_init(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    d, H = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], (d, qr), dt),
        "q_norm": jnp.ones((qr,), dt),
        "wq_b": dense_init(ks[1], (qr, H * (dn + dr)), dt),
        "wkv_a": dense_init(ks[2], (d, kvr + dr), dt),
        "kv_norm": jnp.ones((kvr,), dt),
        "wk_b": dense_init(ks[3], (kvr, H * dn), dt),
        "wv_b": dense_init(ks[4], (kvr, H * dv), dt),
        "wo": dense_init(ks[5], (H * dv, d), dt, scale=1.0 / math.sqrt(H * dv)),
    }


def mla_axes(cfg):
    return {
        "wq_a": ("fsdp", None), "q_norm": (None,),
        "wq_b": ("fsdp", "heads"),
        "wkv_a": ("fsdp", None), "kv_norm": (None,),
        "wk_b": (None, "heads"), "wv_b": (None, "heads"),
        "wo": ("heads", "fsdp"),
    }


def _latents(x, p, cfg, positions):
    """Shared Q latent + KV latent computation."""
    B, S, D = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    dt = x.dtype

    cq = rmsnorm(x @ p["wq_a"].astype(dt), p["q_norm"], cfg.norm_eps)
    q = (cq @ p["wq_b"].astype(dt)).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    kv = x @ p["wkv_a"].astype(dt)                    # (B,S,kvr+dr)
    ckv = rmsnorm(kv[..., : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = rope(kv[..., cfg.kv_lora_rank:][:, :, None, :],
                  positions, cfg.rope_theta)[:, :, 0, :]     # shared across heads
    return q_nope, q_rope, ckv, k_rope


def mla_attention(x, p, cfg, positions, *, cache: MLACache | None = None,
                  layer=None):
    """Returns (y, new_cache).  Absorbed form throughout: scores are computed
    in latent space, so train/prefill and decode share one code path.  A
    decode step with ``layer`` appends to that entry of stacked caches
    (``layers.layer_view``)."""
    B, S, D = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    dt = x.dtype
    scale = 1.0 / math.sqrt(dn + dr)

    q_nope, q_rope, ckv, k_rope = _latents(x, p, cfg, positions)

    # absorb W_uk into the query: q̃ = q_nope · W_uk → latent space
    wk_b = p["wk_b"].astype(dt).reshape(kvr, H, dn)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk_b)        # (B,S,H,kvr)
    q_lat = sh.constrain(q_lat, "batch", "seq", "heads", None)

    if cache is None:
        bq = cfg.attn_q_chunk
        if bq and S > bq and S % bq == 0:
            # blockwise attention over query chunks (see layers.attention)
            outs = []
            for qi in range(S // bq):
                sl = slice(qi * bq, (qi + 1) * bq)
                hi = (qi + 1) * bq
                o = _mla_scores_ctx(
                    q_lat[:, sl], q_rope[:, sl], ckv[:, :hi], k_rope[:, :hi],
                    positions[:, None, :hi] <= positions[:, sl][:, :, None],
                    scale, dt)
                outs.append(o)
            ctx_lat = jnp.concatenate(outs, axis=1)
            new_cache = MLACache(ckv=ckv, krope=k_rope,
                                 length=jnp.full((B,), S, jnp.int32))
            wv_b = p["wv_b"].astype(dt).reshape(kvr, H, dv)
            o = jnp.einsum("bshr,rhd->bshd", ctx_lat, wv_b)
            o = o.reshape(B, S, H * dv)
            y = o @ p["wo"].astype(dt)
            return sh.constrain(y, "batch", "seq", "embed"), new_cache
        keys_lat, keys_rope = ckv, k_rope
        qpos = positions[:, :, None]
        kpos = positions[:, None, :]
        mask = kpos <= qpos                                   # (B,S,S)
        new_cache = MLACache(ckv=ckv, krope=k_rope,
                             length=jnp.full((B,), S, jnp.int32))
    else:
        length = layer_view(cache.length, layer)
        idx = length[0]
        ckv_buf, keys_lat = append_rows(cache.ckv, ckv, idx, layer)
        krope_buf, keys_rope = append_rows(cache.krope, k_rope, idx, layer)
        keys_lat = sh.constrain(keys_lat, "batch", "kv_seq", None)
        keys_rope = sh.constrain(keys_rope, "batch", "kv_seq", None)
        Smax = keys_lat.shape[1]
        mask = (jnp.arange(Smax)[None, None, :] <= idx)       # (1,1,Smax)
        new_cache = MLACache(
            ckv=ckv_buf, krope=krope_buf,
            length=layer_write(cache.length, length + S, layer))

    ctx_lat = _mla_scores_ctx(q_lat, q_rope, keys_lat, keys_rope, mask,
                              scale, dt)
    wv_b = p["wv_b"].astype(dt).reshape(kvr, H, dv)
    o = jnp.einsum("bshr,rhd->bshd", ctx_lat, wv_b)           # absorb W_uv
    o = o.reshape(B, S, H * dv)
    y = o @ p["wo"].astype(dt)
    return sh.constrain(y, "batch", "seq", "embed"), new_cache


def _mla_scores_ctx(q_lat, q_rope, keys_lat, keys_rope, mask, scale, dt):
    """Latent-space attention: scores in compute dtype with stable row stats
    (flash-style numerics; an f32 (B,H,S,S) tensor would not fit HBM at 4k+).
    Returns the attended latent context (B, Sq, H, kvr)."""
    B = q_lat.shape[0]
    s = jnp.einsum("bshr,btr->bhst", q_lat, keys_lat)
    s = s + jnp.einsum("bshd,btd->bhst", q_rope.astype(dt), keys_rope)
    s = s * jnp.asarray(scale, s.dtype)
    m = jnp.broadcast_to(mask, (B,) + mask.shape[1:])
    neg = jnp.asarray(-3e38 if s.dtype == jnp.float32 else -3e4, s.dtype)
    s = jnp.where(m[:, None, ...], s, neg)
    smax = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    prob = jnp.exp(s - smax)
    # bf16 probs end-to-end (see layers._sdpa for the rationale)
    l = jnp.sum(prob, axis=-1, keepdims=True)       # (B,H,Sq,1)
    ctx = jnp.einsum("bhst,btr->bshr", prob, keys_lat)
    return (ctx / jnp.maximum(jnp.transpose(l, (0, 2, 1, 3)), 1e-6)).astype(dt)
