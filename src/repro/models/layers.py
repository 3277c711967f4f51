"""Shared layer library: norms, RoPE, GQA/local attention with KV caches,
gated MLPs, the expert-parallel MoE block, and the drop-free expert layer
for the experts one chip holds.

All layers are pure functions over explicit parameter pytrees (no framework),
cast activations to ``cfg.dtype`` and keep master params in ``cfg.param_dtype``.
Sharding is expressed through logical-axis constraints (see sharding.py).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import sharding as sh


def cdtype(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# initialisation helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    if len(shape) == 3:            # (E, d_in, d_out) expert stacks
        fan_in = shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------


def rmsnorm(x, w, eps=1e-5):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta: float, rotary_dim: int | None = None):
    """x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    rd = rotary_dim or hd
    half = rd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs       # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                             # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rd].astype(jnp.float32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rd:]], axis=-1)


# ---------------------------------------------------------------------------
# attention (GQA, optional sliding window, prefill/decode caches)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jax.Array            # (B, S_max, KV, hd)
    v: jax.Array
    length: jax.Array       # (B,) — filled positions


# A decode step over a scanned stack of layers passes each block its
# group's caches whole, every leaf stacking the layers' on axis 0, with the
# index ``layer`` of its own.  The block reads its entry and writes back only
# what the step changes, so a donated stack is updated in place.  With
# ``layer`` None a leaf is the block's own.


def layer_view(buf, layer=None):
    """A layer's cache (any pytree): ``buf``, or entry ``layer`` of each of
    its stacked leaves."""
    if layer is None:
        return buf
    return jax.tree.map(
        lambda b: jax.lax.dynamic_index_in_dim(b, layer, keepdims=False), buf)


def layer_write(buf, new, layer=None):
    """``buf`` with a layer's cache set to ``new``, a pytree alike: ``new``
    itself, or each stacked leaf with ``new``'s written whole at entry
    ``layer``."""
    if layer is None:
        return new
    return jax.tree.map(
        lambda b, n: jax.lax.dynamic_update_index_in_dim(
            b, n.astype(b.dtype), layer, 0), buf, new)


def append_rows(buf, rows, idx, layer=None):
    """(buf, view): ``rows`` (B, S, ...) written at position ``idx`` of a
    layer's sequence cache, and that layer's cache holding them, to attend
    over.  Into a stack only the rows are written, at entry ``layer``, and
    the view is the entry as read before, with the rows put in: read back
    after the write, the TPU compiler lays the whole stack out for the
    attention's read and copies it whole into and out of the layer loop."""
    rows = rows.astype(buf.dtype)
    if layer is None:
        buf = jax.lax.dynamic_update_slice_in_dim(buf, rows, idx, axis=1)
        return buf, buf
    view = jax.lax.dynamic_update_slice_in_dim(layer_view(buf, layer), rows,
                                               idx, axis=1)
    start = (layer, 0, idx) + (0,) * (buf.ndim - 3)
    buf = jax.lax.dynamic_update_slice(buf, rows[None], start)
    return buf, view


def attn_params_init(key, cfg, d_model=None):
    d = d_model or cfg.d_model
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dt),
        "wk": dense_init(ks[1], (d, KV * hd), dt),
        "wv": dense_init(ks[2], (d, KV * hd), dt),
        "wo": dense_init(ks[3], (H * hd, d), dt, scale=1.0 / math.sqrt(H * hd)),
    }
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((H * hd,), dt), bk=jnp.zeros((KV * hd,), dt),
                 bv=jnp.zeros((KV * hd,), dt))
    return p


def attn_axes(cfg):
    a = {"wq": ("fsdp", "heads"), "wk": ("fsdp", "kv_heads"),
         "wv": ("fsdp", "kv_heads"), "wo": ("heads", "fsdp")}
    if cfg.qkv_bias:
        a.update(bq=("heads",), bk=("kv_heads",), bv=("kv_heads",))
    return a


def _qkv(x, p, cfg):
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    return q, k, v


def _sdpa(q, k, v, mask, scale: float | None = None):
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd), mask bool broadcastable to (B,Sq,Sk);
    scores scaled by ``scale`` (default 1/sqrt(hd)).

    Scores/probs stay in the compute dtype (bf16 in production configs) with
    f32 row statistics and f32 PV accumulation — the XLA analogue of a flash
    kernel's numerics without materialising an O(S²) f32 tensor (which is
    what blows the HBM budget at 4k+ sequence lengths)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qs = (q * jnp.asarray(scale, q.dtype)).reshape(B, Sq, KV, g, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qs, k)            # compute dtype
    m = jnp.broadcast_to(mask, (B,) + mask.shape[1:])
    neg = jnp.asarray(-3e38 if s.dtype == jnp.float32 else -3e4, s.dtype)
    s = jnp.where(m[:, None, None, ...] if m.ndim == 3 else m, s, neg)
    smax = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - smax)
    # probs stay in the compute dtype end-to-end: an f32 row-sum would pull
    # the entire O(S²) backward chain into f32 (+converts) — measured 4×
    # HBM-traffic inflation.  Flash kernels also feed bf16 probs to the MXU.
    l = jnp.sum(p, axis=-1, keepdims=True)          # (B,KV,g,Sq,1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v)       # unnormalised
    o = o / jnp.maximum(jnp.transpose(l, (0, 3, 1, 2, 4)), 1e-6)
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def attention(x, p, cfg, positions, *, window: int = 0,
              cache: KVCache | None = None, layer=None):
    """Returns (y, new_cache).  Train/prefill: cache=None builds causal (or
    windowed) self-attention and returns the fresh cache for serving.  Decode:
    S==1 step appended to the cache (to entry ``layer`` of stacked caches,
    see ``layer_view``).  ``cfg.position_embedding`` "nope" applies no
    positions; ``cfg.attention_multiplier`` scales the scores."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    scale = cfg.attention_multiplier or None
    q, k, v = _qkv(x, p, cfg)
    # rope_theta=0 → absolute positions at the embedding (whisper)
    if cfg.rope_theta and cfg.position_embedding == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # Shard attention by Q heads.  When kv heads don't cover the model axis
    # (GQA with small kv), replicate K/V heads instead of letting GSPMD split
    # the head_dim — that path triggers involuntary full rematerialisation.
    q = sh.constrain(q, "batch", "seq", "heads", None)
    kv_ok = KV % max(sh.axis_size("kv_heads"), 1) == 0
    k = sh.constrain(k, "batch", "seq", "kv_heads" if kv_ok else None, None)
    v = sh.constrain(v, "batch", "seq", "kv_heads" if kv_ok else None, None)

    if cache is None:
        bq = cfg.attn_q_chunk
        if bq and S > bq and S % bq == 0:
            # blockwise (flash-style) attention: tile the query loop — the
            # paper's Tile transformation applied to the attention nest.  The
            # per-block score tensor is (B, bq, ≤S); causal blocks also slice
            # KV to the block's horizon (static slices → exact HLO cost).
            outs = []
            for qi in range(S // bq):
                qb = q[:, qi * bq:(qi + 1) * bq]
                posb = positions[:, qi * bq:(qi + 1) * bq]
                hi = (qi + 1) * bq        # causal horizon of this block
                kb, vb = k[:, :hi], v[:, :hi]
                kposb = positions[:, None, :hi]
                mask = kposb <= posb[:, :, None]
                if window:
                    mask = mask & (kposb > posb[:, :, None] - window)
                outs.append(_sdpa(qb, kb, vb, mask, scale))
            y = jnp.concatenate(outs, axis=1)
        else:
            qpos = positions[:, :, None]              # (B,S,1)
            kpos = positions[:, None, :]              # (B,1,S)
            mask = kpos <= qpos
            if window:
                mask = mask & (kpos > qpos - window)
            y = _sdpa(q, k, v, mask, scale)
        new_cache = KVCache(k=k, v=v, length=jnp.full((B,), S, jnp.int32))
    else:
        # decode: append this step, attend over valid prefix
        length = layer_view(cache.length, layer)
        idx = length[0]                               # uniform fill pointer
        kbuf, kc = append_rows(cache.k, k, idx, layer)
        vbuf, vc = append_rows(cache.v, v, idx, layer)
        kc = sh.constrain(kc, "batch", "kv_seq", "kv_heads", None)
        vc = sh.constrain(vc, "batch", "kv_seq", "kv_heads", None)
        Smax = kc.shape[1]
        kpos = jnp.arange(Smax)[None, None, :]        # (1,1,Smax)
        valid = kpos <= idx
        if window:
            valid = valid & (kpos > idx - window)
        y = _sdpa(q, kc, vc, valid, scale)
        new_cache = KVCache(k=kbuf, v=vbuf,
                            length=layer_write(cache.length, length + S, layer))

    y = y.reshape(B, S, H * hd)
    y = y @ p["wo"].astype(y.dtype)
    return sh.constrain(y, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_params_init(key, cfg, d_ff=None, d_model=None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    if cfg.act == "gelu":          # non-gated (whisper)
        return {"wi": dense_init(ks[0], (d, f), dt),
                "bi": jnp.zeros((f,), dt),
                "wo": dense_init(ks[1], (f, d), dt),
                "bo": jnp.zeros((d,), dt)}
    return {"gate": dense_init(ks[0], (d, f), dt),
            "up": dense_init(ks[1], (d, f), dt),
            "down": dense_init(ks[2], (f, d), dt)}


def mlp_axes(cfg):
    if cfg.act == "gelu":
        return {"wi": ("fsdp", "ff"), "bi": ("ff",),
                "wo": ("ff", "fsdp"), "bo": ("embed",)}
    return {"gate": ("fsdp", "ff"), "up": ("fsdp", "ff"),
            "down": ("ff", "fsdp")}


def mlp(x, p, cfg):
    dt = x.dtype
    if cfg.act == "gelu":
        h = jax.nn.gelu(x @ p["wi"].astype(dt) + p["bi"].astype(dt))
        return h @ p["wo"].astype(dt) + p["bo"].astype(dt)
    act = jax.nn.gelu if cfg.act == "gelu_gated" else jax.nn.silu
    h = act(x @ p["gate"].astype(dt)) * (x @ p["up"].astype(dt))
    h = sh.constrain(h, "batch", "seq", "ff")
    return h @ p["down"].astype(dt)


# ---------------------------------------------------------------------------
# Mixture of Experts (expert-parallel, capacity-factor dropping)
# ---------------------------------------------------------------------------


def moe_params_init(key, cfg):
    """The router scores all ``n_experts``; the stacks hold the experts
    held here (``n_experts_held``, all when unset)."""
    dt = jnp.dtype(cfg.param_dtype)
    edt = jnp.dtype(cfg.expert_dtype) if cfg.expert_dtype else dt
    d, fm, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    held = cfg.n_experts_held or E
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, E), dt, scale=0.02),
        "experts": {
            "gate": dense_init(ks[1], (held, d, fm), edt),
            "up": dense_init(ks[2], (held, d, fm), edt),
            "down": dense_init(ks[3], (held, fm, d), edt,
                               scale=1.0 / math.sqrt(fm)),
        },
    }
    if cfg.n_shared_experts:
        fs = cfg.shared_d_ff or cfg.moe_d_ff * cfg.n_shared_experts
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {"gate": dense_init(kss[0], (d, fs), dt),
                       "up": dense_init(kss[1], (d, fs), dt),
                       "down": dense_init(kss[2], (fs, d), dt)}
    return p


def moe_axes(cfg):
    a = {"router": ("embed", None),
         "experts": {"gate": ("experts", "fsdp", "moe_ff"),
                     "up": ("experts", "fsdp", "moe_ff"),
                     # down (E, fm, d): shard fm over fsdp so the shard_map
                     # body gathers every expert mat along axis=1 uniformly
                     "down": ("experts", "fsdp", None)}}
    if cfg.n_shared_experts:
        a["shared"] = {"gate": ("fsdp", "ff"), "up": ("fsdp", "ff"),
                       "down": ("ff", "fsdp")}
    return a


def _moe_local(x2d, router_w, we, cfg, ep_axis: str | None):
    """Token dispatch → (expert-parallel all_to_all) → grouped GEMM → combine.

    x2d: (T, D) local tokens.  we: expert weights, local shard (E_loc on dim 0)
    when ep_axis is set, full (E, ...) otherwise.  Returns (y (T,D), aux loss).
    """
    T, D = x2d.shape
    E, k = cfg.n_experts, cfg.top_k
    ep = 1
    if ep_axis is not None:
        ep = jax.lax.axis_size(ep_axis)
    E_loc = E // ep

    logits = (x2d @ router_w.astype(x2d.dtype)).astype(jnp.float32)   # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                            # (T,k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss (local estimate; psum'd below)
    me = probs.mean(axis=0)                                           # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(
        1.0 / (T * k))
    aux = E * jnp.sum(me * ce)

    C = max(1, math.ceil(k * T / E * cfg.capacity_factor))

    flat_ids = top_i.reshape(-1)                                      # (T·k,)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    counts = jnp.bincount(flat_ids, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(T * k) - starts[sorted_ids]
    keep = pos_sorted < C
    dest_sorted = jnp.where(keep, sorted_ids * C + pos_sorted, E * C)
    # slot of each (token, k) pair in flat order
    dest = jnp.zeros((T * k,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32))

    src_token = order // k
    buf = jnp.zeros((E * C, D), x2d.dtype).at[dest_sorted].set(
        x2d[src_token], mode="drop")
    buf = buf.reshape(E, C, D)

    if ep_axis is not None:
        # (E, C, D) = (ep·E_loc, C, D) → peers exchange expert shards:
        # receive (E_loc, ep·C, D)
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)

    act = jax.nn.silu
    h = act(jnp.einsum("ecd,edf->ecf", buf, we["gate"].astype(buf.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, we["up"].astype(buf.dtype))
    out = jnp.einsum("ecf,efd->ecd", h, we["down"].astype(buf.dtype))

    if ep_axis is not None:
        out = jax.lax.all_to_all(out, ep_axis, split_axis=1, concat_axis=0,
                                 tiled=True)                          # (E, C, D)
    out = out.reshape(E * C, D)
    out = jnp.concatenate([out, jnp.zeros((1, D), out.dtype)], axis=0)

    gathered = out[jnp.minimum(dest, E * C)]                          # (T·k, D)
    w_flat = top_w.reshape(-1, 1).astype(gathered.dtype)
    dropped = (dest == E * C)[:, None]
    y = jnp.where(dropped, 0.0, gathered * w_flat).reshape(T, k, D).sum(axis=1)

    if ep_axis is not None:
        aux = jax.lax.pmean(aux, ep_axis)
    return y, aux


def moe_ffn(x, p, cfg):
    """x: (B,S,D) → (y, aux_loss).  Uses expert-parallel shard_map when a mesh
    is installed, plain local dispatch otherwise (smoke tests)."""
    B, S, D = x.shape
    mesh = sh.mesh()
    ep_axis = None
    rules = sh.rules() or {}
    if mesh is not None:
        e = rules.get("experts")
        if isinstance(e, str):
            ep_axis = e

    if mesh is None or ep_axis is None:
        x2d = x.reshape(B * S, D)
        y, aux = _moe_local(x2d, p["router"], p["experts"], cfg, None)
        y = y.reshape(B, S, D)
    else:
        token_spec = sh.spec("batch", "seq", None)
        # tokens additionally split over the EP axis when seq allows
        seq_over_ep = S % mesh.shape[ep_axis] == 0 and S >= mesh.shape[ep_axis]
        if seq_over_ep and token_spec[1] is None:
            parts = list(token_spec)
            parts[1] = ep_axis
            token_spec = P(*parts)

        # expert weights are stored FSDP-sharded (ZeRO-3) over the data axes
        # and gathered transiently per layer inside the shard_map body
        fsdp = rules.get("fsdp")
        fsdp_axes = (fsdp,) if isinstance(fsdp, str) else tuple(fsdp or ())
        fsdp_axes = tuple(a for a in fsdp_axes if a in mesh.axis_names)

        def body(x_loc, router_w, we):
            b, s, d = x_loc.shape
            if fsdp_axes:
                we = {
                    "gate": jax.lax.all_gather(we["gate"], fsdp_axes, axis=1,
                                               tiled=True),
                    "up": jax.lax.all_gather(we["up"], fsdp_axes, axis=1,
                                             tiled=True),
                    "down": jax.lax.all_gather(we["down"], fsdp_axes, axis=1,
                                               tiled=True),
                }
            y, aux = _moe_local(x_loc.reshape(b * s, d), router_w, we, cfg,
                                ep_axis)
            # aux already pmean'd over EP; mean over the token axes too
            other = tuple(a for a in mesh.axis_names if a != ep_axis)
            if other:
                aux = jax.lax.pmean(aux, other)
            return y.reshape(b, s, d), aux

        egate = sh.spec("experts", "fsdp", None)
        edown = sh.spec("experts", "fsdp", None)   # down: (E, fm, d) — shard fm
        # check_vma=False: when tokens are not model-sharded (decode, S=1)
        # every model shard computes identical outputs from identical inputs —
        # replication holds by construction but cannot be statically inferred
        # through the all_to_all (verified numerically in tests/test_system).
        y, aux = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(token_spec, P(None, None),
                      {"gate": egate, "up": egate, "down": edown}),
            out_specs=(token_spec, P()),
            check_vma=False,
        )(x, p["router"], p["experts"])

    if cfg.n_shared_experts:
        dt = x.dtype
        sp = p["shared"]
        h = jax.nn.silu(x @ sp["gate"].astype(dt)) * (x @ sp["up"].astype(dt))
        h = sh.constrain(h, "batch", "seq", "ff")
        y = y + h @ sp["down"].astype(dt)
    return sh.constrain(y, "batch", "seq", "embed"), aux


# ---------------------------------------------------------------------------
# The experts one chip holds: routed over all, computed for its own, no drops
# ---------------------------------------------------------------------------


def routed_share(x2d, router_w, we, top_k: int, first: int = 0):
    """The part of an MoE layer's routed output that the experts held here
    give, for every token routed to them.

    x2d: (T, D).  ``router_w`` (D, E) scores every expert of the layer;
    ``we`` holds experts ``first .. first + E_h - 1`` (E_h on dim 0).  Each
    token keeps its top ``top_k`` experts, weighted by a softmax over the
    chosen logits (the same weights as renormalising a softmax over all E).
    There is no capacity: a held expert computes every token that picked it.
    The product runs over the held experts, gated by the routing weights
    (zero where a token did not pick the expert).  At a decode step's few
    tokens each held expert's weights are read whatever the routing, and
    this needs no sort and no shapes that depend on the routing; a prefill
    computes E_h rows a token where ``top_k`` · E_h / E are routed.

    Returns (y (T, D), rows): ``rows`` counts the (token, held expert)
    pairs the routing picked, as int32.
    """
    E_h = we["gate"].shape[0]
    dt = x2d.dtype
    logits = (x2d @ router_w.astype(dt)).astype(jnp.float32)          # (T,E)
    top_l, top_i = jax.lax.top_k(logits, top_k)
    w = jax.nn.softmax(top_l, axis=-1)                                 # (T,k)
    idx = top_i - first
    held = (idx >= 0) & (idx < E_h)
    gate = jnp.einsum("tk,tke->te", w,
                      jax.nn.one_hot(idx, E_h, dtype=jnp.float32))     # (T,E_h)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x2d, we["gate"].astype(dt)))
    h = h * jnp.einsum("td,edf->tef", x2d, we["up"].astype(dt))
    h = h * gate.astype(dt)[..., None]
    y = jnp.einsum("tef,efd->td", h, we["down"].astype(dt))
    return y, jnp.sum(held, dtype=jnp.int32)


def expert_ffn(x, p, cfg):
    """x: (B,S,D) → (y, rows): the held experts' routed part plus the
    shared MLP, which every chip computes whole.  On one chip there is no
    exchange: what absent experts would add is left out."""
    B, S, D = x.shape
    y, rows = routed_share(x.reshape(B * S, D), p["router"], p["experts"],
                           cfg.top_k)
    y = y.reshape(B, S, D) + mlp(x, p["shared"], cfg)
    return sh.constrain(y, "batch", "seq", "embed"), rows
