"""RecurrentGemma / Griffin blocks (arXiv:2402.19427).

Recurrent block: dual-branch (gate ⊙ RG-LRU(conv1d(x-branch))), where the
RG-LRU is a gated diagonal linear recurrence
    a_t = exp(-c·softplus(Λ)·r_t),   h_t = a_t h_{t-1} + √(1−a_t²)·(i_t ⊙ x_t)
computed with an associative scan for train/prefill and a single-step update
for decode.  Attention blocks are local (sliding-window 2048) MQA — handled by
``layers.attention(window=...)``.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import sharding as sh
from .layers import dense_init, layer_view, layer_write

_C = 8.0          # Griffin's fixed gate sharpness


class RecurrentCache(NamedTuple):
    conv: jax.Array          # (B, K-1, W) trailing conv inputs
    h: jax.Array             # (B, W) RG-LRU hidden state


def rec_params_init(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    d, w = cfg.d_model, cfg.lru_width
    ks = jax.random.split(key, 7)
    # Λ init so that a ∈ [0.9, 0.999] at r=1 (paper appendix)
    lam = jnp.log(jnp.expm1(-jnp.log(
        jnp.linspace(0.9, 0.999, w).astype(jnp.float32)) / _C))
    return {
        "in_x": dense_init(ks[0], (d, w), dt),
        "in_gate": dense_init(ks[1], (d, w), dt),
        "conv_w": dense_init(ks[2], (cfg.conv_kernel, w), dt, scale=0.3),
        "conv_b": jnp.zeros((w,), dt),
        "wr": dense_init(ks[3], (w, w), dt),
        "br": jnp.zeros((w,), dt),
        "wi": dense_init(ks[4], (w, w), dt),
        "bi": jnp.zeros((w,), dt),
        "lam": lam.astype(dt),
        "out": dense_init(ks[5], (w, d), dt),
    }


def rec_axes(cfg):
    return {"in_x": ("fsdp", "lru"), "in_gate": ("fsdp", "lru"),
            "conv_w": (None, "lru"), "conv_b": ("lru",),
            "wr": ("fsdp", "lru"), "br": ("lru",),
            "wi": ("fsdp", "lru"), "bi": ("lru",),
            "lam": ("lru",), "out": ("lru", "fsdp")}


def _causal_conv(x, w, b, state=None):
    """x: (B,S,W); w: (K,W) depthwise.  state: (B,K-1,W) trailing context."""
    B, S, W = x.shape
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((B, K - 1, W), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)            # (B, S+K-1, W)
    y = sum(xp[:, i : i + S, :] * w[i].astype(x.dtype) for i in range(K))
    new_state = xp[:, -(K - 1):, :] if K > 1 else jnp.zeros((B, 0, W), x.dtype)
    return y + b.astype(x.dtype), new_state


def _rg_lru_scan(x, r, i, lam):
    """Associative linear recurrence h_t = a_t·h_{t-1} + b_t over axis 1."""
    log_a = -_C * jax.nn.softplus(lam.astype(jnp.float32)) * r    # (B,S,W)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (
        i * x.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    a_seq, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
    return h, a, gated


def recurrent_block(x, p, cfg, *, cache: RecurrentCache | None = None,
                    layer=None):
    """Returns (y, new_cache).  x: (B,S,D).  With ``layer``, ``cache``
    stacks every layer's state and the step writes entry ``layer``'s
    (``layers.layer_view``)."""
    B, S, D = x.shape
    dt = x.dtype
    gate = jax.nn.gelu(x @ p["in_gate"].astype(dt))
    xb = x @ p["in_x"].astype(dt)
    xb = sh.constrain(xb, "batch", "seq", "lru")

    state = layer_view(cache, layer)
    conv_state = state.conv if state is not None else None
    xb, new_conv = _causal_conv(xb, p["conv_w"], p["conv_b"], conv_state)

    r = jax.nn.sigmoid((xb @ p["wr"].astype(dt) + p["br"].astype(dt))
                       .astype(jnp.float32))
    i = jax.nn.sigmoid((xb @ p["wi"].astype(dt) + p["bi"].astype(dt))
                       .astype(jnp.float32))

    if cache is None:
        h, _, _ = _rg_lru_scan(xb, r, i, p["lam"])
        new_h = h[:, -1, :]
    else:
        log_a = -_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r[:, 0]
        a = jnp.exp(log_a)
        b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (
            i[:, 0] * xb[:, 0].astype(jnp.float32))
        new_h = a * state.h.astype(jnp.float32) + b
        h = new_h[:, None, :]

    y = (h.astype(dt) * gate) @ p["out"].astype(dt)
    y = sh.constrain(y, "batch", "seq", "embed")
    new_cache = RecurrentCache(conv=new_conv.astype(x.dtype),
                               h=new_h.astype(jnp.float32))
    return y, layer_write(cache, new_cache, layer)
