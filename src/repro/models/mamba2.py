"""Mamba-2 mixer (SSD — state-space duality, arXiv:2405.21060).

Train/prefill use the chunked SSD algorithm (batched version of
``kernels/ref.ssd_ref_chunked``; the Pallas kernel in ``kernels/ssd.py``
implements the same schedule for TPU).  The chunk length ``cfg.ssd_chunk`` is
a tile size in the paper's search space.  Decode is the O(1) recurrence on the
(H, P, N) state.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import sharding as sh
from .layers import dense_init, layer_view, layer_write, rmsnorm


class SSMCache(NamedTuple):
    conv: jax.Array          # (B, K-1, conv_channels)
    h: jax.Array             # (B, H, P, N) ssm state (f32)


def _dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_headdim
    G, N, P = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_headdim
    conv_ch = d_in + 2 * G * N
    return d_in, H, G, N, P, conv_ch


def mamba_params_init(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    d_in, H, G, N, P, conv_ch = _dims(cfg)
    ks = jax.random.split(key, 4)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * d_in + 2 * G * N + H), dt),
        "conv_w": dense_init(ks[1], (cfg.conv_kernel, conv_ch), dt, scale=0.3),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "a_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)).astype(dt),
        "d_skip": jnp.ones((H,), dt),
        "dt_bias": jnp.zeros((H,), dt),
        "norm_w": jnp.ones((d_in,), dt),
        "out_proj": dense_init(ks[2], (d_in, d), dt),
    }


def mamba_axes(cfg):
    return {"in_proj": ("fsdp", "ff"), "conv_w": (None, "ff"),
            "conv_b": ("ff",), "a_log": ("ssm_heads",), "d_skip": ("ssm_heads",),
            "dt_bias": ("ssm_heads",), "norm_w": ("ff",),
            "out_proj": ("ff", "fsdp")}


def _ssd_chunked(x, dtv, a, b, c, chunk):
    """Batched chunked SSD.  x: (B,L,H,P); dtv: (B,L,H); a: (H,);
    b,c: (B,L,G,N) head-grouped.  Returns y (B,L,H,P), final state (B,H,P,N)."""
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    hpg = H // G
    ch = min(chunk, L)
    assert L % ch == 0
    nc = L // ch

    xf = x.astype(jnp.float32).reshape(B, nc, ch, H, P)
    dtf = dtv.astype(jnp.float32).reshape(B, nc, ch, H)
    bf = jnp.repeat(b.astype(jnp.float32), hpg, axis=2).reshape(B, nc, ch, H, N)
    cf = jnp.repeat(c.astype(jnp.float32), hpg, axis=2).reshape(B, nc, ch, H, N)

    la = dtf * a[None, None, None, :]                 # log decay
    cum = jnp.cumsum(la, axis=2)                      # (B,nc,ch,H) inclusive
    mask = jnp.tril(jnp.ones((ch, ch), bool))
    # mask BEFORE exp: the strictly-upper entries have positive exponents that
    # overflow (and poison gradients through the jnp.where)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]
    seg = jnp.where(mask[None, None, :, :, None], seg, -1e30)
    decay = jnp.exp(seg)
    scores = jnp.einsum("bnthk,bnshk->bntsh", cf, bf) * decay
    y_intra = jnp.einsum("bntsh,bnsh,bnshp->bnthp", scores, dtf, xf)

    # chunk summaries: state contribution of each chunk and its total decay
    total = cum[:, :, -1, :]                          # (B,nc,H)
    w = jnp.exp(total[:, :, None, :] - cum) * dtf     # (B,nc,ch,H)
    chunk_state = jnp.einsum("bnsh,bnshk,bnshp->bnhpk", w, bf, xf)

    # scan over chunks: h_{n} = exp(total_n)·h_{n-1} + chunk_state_n
    def step(h, inp):
        tot, cs = inp
        h = jnp.exp(tot)[..., None, None] * h + cs
        return h, h

    h0 = jnp.zeros((B, H, P, N), jnp.float32)
    hT, h_after = jax.lax.scan(
        step, h0, (jnp.moveaxis(total, 1, 0), jnp.moveaxis(chunk_state, 1, 0)))
    h_before = jnp.concatenate([h0[None], h_after[:-1]], axis=0)  # state entering chunk n
    h_before = jnp.moveaxis(h_before, 0, 1)                       # (B,nc,H,P,N)

    y_inter = jnp.einsum("bnthk,bnhpk,bnth->bnthp", cf, h_before, jnp.exp(cum))
    y = (y_intra + y_inter).reshape(B, L, H, P)
    return y, hT


def mamba_block(x, p, cfg, *, cache: SSMCache | None = None, layer=None):
    """Returns (y, new_cache).  x: (B,S,D).  With ``layer``, ``cache``
    stacks every layer's state and the step writes entry ``layer``'s
    (``layers.layer_view``)."""
    from .griffin import _causal_conv

    B, S, D = x.shape
    d_in, H, G, N, P, conv_ch = _dims(cfg)
    dt_ = x.dtype

    zxbcdt = x @ p["in_proj"].astype(dt_)             # (B,S,2*d_in+2GN+H)
    z, xbc, dt_raw = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * G * N], axis=-1)
    xbc = sh.constrain(xbc, "batch", "seq", None)

    state = layer_view(cache, layer)
    conv_state = state.conv if state is not None else None
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xbc = jax.nn.silu(xbc)

    xs, b, c = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    xs = xs.reshape(B, S, H, P)
    b = b.reshape(B, S, G, N)
    c = c.reshape(B, S, G, N)
    dtv = jax.nn.softplus(dt_raw.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))     # (B,S,H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                  # (H,) negative

    if cache is None:
        y, hT = _ssd_chunked(xs, dtv, a, b, c, cfg.ssd_chunk)
    else:
        hpg = H // G
        bg = jnp.repeat(b[:, 0], hpg, axis=1)         # (B,H,N)
        cg = jnp.repeat(c[:, 0], hpg, axis=1)
        decay = jnp.exp(dtv[:, 0] * a[None, :])       # (B,H)
        upd = (dtv[:, 0, :, None] * xs[:, 0].astype(jnp.float32))[..., None] \
            * bg[:, :, None, :].astype(jnp.float32)   # (B,H,P,N)
        hT = decay[..., None, None] * state.h + upd
        y = jnp.einsum("bhpn,bhn->bhp", hT, cg.astype(jnp.float32))[:, None]

    y = y + xs.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(B, S, d_in).astype(dt_)
    # gated RMSNorm (mamba2's norm before out_proj)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    out = y @ p["out_proj"].astype(dt_)
    out = sh.constrain(out, "batch", "seq", "embed")
    new_cache = SSMCache(conv=new_conv.astype(x.dtype), h=hT)
    return out, layer_write(cache, new_cache, layer)
