"""Block-level composition: one function + param-init + axes per block kind.

Kinds:
  dense      pre-norm GQA attention + gated MLP           (qwen/internlm/glm/phi3v)
  mla_dense  MLA attention + gated MLP                    (deepseek dense prefix)
  moe        GQA attention + MoE FFN (+ shared experts)   (kimi)
  mla_moe    MLA attention + MoE FFN                      (deepseek)
  rec        RG-LRU recurrent block + GeGLU MLP           (recurrentgemma)
  lattn      local (sliding-window) MQA attention + MLP   (recurrentgemma)
  mamba      Mamba-2 SSD mixer                            (mamba2)
  mamba_moe  Mamba-2 SSD mixer + held-expert FFN          (granite-4.0-h)
  attn_moe   GQA attention (NoPE, scaled) + held-expert FFN (granite-4.0-h)
  enc        bidirectional attention + GELU MLP (LN+bias) (whisper encoder)
  dec        causal self-attn + cross-attn + GELU MLP     (whisper decoder)

The held-expert kinds route over all experts and compute the experts held
here without dropping a token (``layers.expert_ffn``), and scale both
sublayers' outputs by ``cfg.residual_multiplier`` before their residual add.

Every block returns ``(x, cache, aux)`` so stacks can be scanned uniformly:
aux = MoE load-balance loss for ``moe``/``mla_moe`` (0.0 elsewhere), and
``{"expert_rows": n}`` for the held-expert kinds, the int32 count of
(token, held expert) pairs their layer computed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import sharding as sh
from .griffin import RecurrentCache, rec_axes, rec_params_init, recurrent_block
from .layers import (
    KVCache,
    attention,
    attn_axes,
    attn_params_init,
    dense_init,
    expert_ffn,
    layer_view,
    layernorm,
    mlp,
    mlp_axes,
    mlp_params_init,
    moe_axes,
    moe_ffn,
    moe_params_init,
    rmsnorm,
)
from .mamba2 import SSMCache, mamba_axes, mamba_block, mamba_params_init
from .mla import MLACache, mla_attention, mla_axes, mla_params_init


def _norm_init(cfg, with_bias=False):
    dt = jnp.dtype(cfg.param_dtype)
    if with_bias:
        return {"w": jnp.ones((cfg.d_model,), dt), "b": jnp.zeros((cfg.d_model,), dt)}
    return {"w": jnp.ones((cfg.d_model,), dt)}


def _norm(x, p, cfg):
    if "b" in p:
        return layernorm(x, p["w"], p["b"], cfg.norm_eps)
    return rmsnorm(x, p["w"], cfg.norm_eps)


_NORM_AXES = {"w": ("embed",)}
_NORM_AXES_B = {"w": ("embed",), "b": ("embed",)}


# ---------------------------------------------------------------------------


def init_block(kind: str, key, cfg):
    ks = jax.random.split(key, 4)
    if kind in ("dense", "lattn"):
        return {"ln1": _norm_init(cfg), "attn": attn_params_init(ks[0], cfg),
                "ln2": _norm_init(cfg), "mlp": mlp_params_init(ks[1], cfg)}
    if kind == "mla_dense":
        return {"ln1": _norm_init(cfg), "attn": mla_params_init(ks[0], cfg),
                "ln2": _norm_init(cfg), "mlp": mlp_params_init(ks[1], cfg)}
    if kind == "moe":
        return {"ln1": _norm_init(cfg), "attn": attn_params_init(ks[0], cfg),
                "ln2": _norm_init(cfg), "moe": moe_params_init(ks[1], cfg)}
    if kind == "mla_moe":
        return {"ln1": _norm_init(cfg), "attn": mla_params_init(ks[0], cfg),
                "ln2": _norm_init(cfg), "moe": moe_params_init(ks[1], cfg)}
    if kind == "rec":
        return {"ln1": _norm_init(cfg), "rec": rec_params_init(ks[0], cfg),
                "ln2": _norm_init(cfg), "mlp": mlp_params_init(ks[1], cfg)}
    if kind == "mamba":
        return {"ln": _norm_init(cfg), "mixer": mamba_params_init(ks[0], cfg)}
    if kind == "mamba_moe":
        return {"ln1": _norm_init(cfg), "mixer": mamba_params_init(ks[0], cfg),
                "ln2": _norm_init(cfg), "moe": moe_params_init(ks[1], cfg)}
    if kind == "attn_moe":
        return {"ln1": _norm_init(cfg), "attn": attn_params_init(ks[0], cfg),
                "ln2": _norm_init(cfg), "moe": moe_params_init(ks[1], cfg)}
    if kind == "enc":
        return {"ln1": _norm_init(cfg, True), "attn": attn_params_init(ks[0], cfg),
                "ln2": _norm_init(cfg, True), "mlp": mlp_params_init(ks[1], cfg)}
    if kind == "dec":
        return {"ln1": _norm_init(cfg, True), "attn": attn_params_init(ks[0], cfg),
                "lnx": _norm_init(cfg, True), "xattn": attn_params_init(ks[1], cfg),
                "ln2": _norm_init(cfg, True), "mlp": mlp_params_init(ks[2], cfg)}
    raise KeyError(kind)


def block_axes(kind: str, cfg):
    if kind in ("dense", "lattn"):
        return {"ln1": _NORM_AXES, "attn": attn_axes(cfg),
                "ln2": _NORM_AXES, "mlp": mlp_axes(cfg)}
    if kind == "mla_dense":
        return {"ln1": _NORM_AXES, "attn": mla_axes(cfg),
                "ln2": _NORM_AXES, "mlp": mlp_axes(cfg)}
    if kind == "moe":
        return {"ln1": _NORM_AXES, "attn": attn_axes(cfg),
                "ln2": _NORM_AXES, "moe": moe_axes(cfg)}
    if kind == "mla_moe":
        return {"ln1": _NORM_AXES, "attn": mla_axes(cfg),
                "ln2": _NORM_AXES, "moe": moe_axes(cfg)}
    if kind == "rec":
        return {"ln1": _NORM_AXES, "rec": rec_axes(cfg),
                "ln2": _NORM_AXES, "mlp": mlp_axes(cfg)}
    if kind == "mamba":
        return {"ln": _NORM_AXES, "mixer": mamba_axes(cfg)}
    if kind == "mamba_moe":
        return {"ln1": _NORM_AXES, "mixer": mamba_axes(cfg),
                "ln2": _NORM_AXES, "moe": moe_axes(cfg)}
    if kind == "attn_moe":
        return {"ln1": _NORM_AXES, "attn": attn_axes(cfg),
                "ln2": _NORM_AXES, "moe": moe_axes(cfg)}
    if kind == "enc":
        return {"ln1": _NORM_AXES_B, "attn": attn_axes(cfg),
                "ln2": _NORM_AXES_B, "mlp": mlp_axes(cfg)}
    if kind == "dec":
        return {"ln1": _NORM_AXES_B, "attn": attn_axes(cfg),
                "lnx": _NORM_AXES_B, "xattn": attn_axes(cfg),
                "ln2": _NORM_AXES_B, "mlp": mlp_axes(cfg)}
    raise KeyError(kind)


def init_cache(kind: str, cfg, batch: int, s_max: int, enc_seq: int = 0):
    """Empty serving cache for one block of this kind."""
    from .mamba2 import _dims

    cdt = jnp.dtype(cfg.dtype)
    if kind in ("dense", "moe", "enc", "attn_moe"):
        KV, hd = cfg.n_kv_heads, cfg.head_dim
        return KVCache(k=jnp.zeros((batch, s_max, KV, hd), cdt),
                       v=jnp.zeros((batch, s_max, KV, hd), cdt),
                       length=jnp.zeros((batch,), jnp.int32))
    if kind == "lattn":
        KV, hd = cfg.n_kv_heads, cfg.head_dim
        s = min(s_max, cfg.window) if cfg.window else s_max
        # ring-less window cache: we keep full s_max for index simplicity at
        # dry-run scale; the window mask bounds the attention cost.
        return KVCache(k=jnp.zeros((batch, s_max, KV, hd), cdt),
                       v=jnp.zeros((batch, s_max, KV, hd), cdt),
                       length=jnp.zeros((batch,), jnp.int32))
    if kind in ("mla_dense", "mla_moe"):
        return MLACache(
            ckv=jnp.zeros((batch, s_max, cfg.kv_lora_rank), cdt),
            krope=jnp.zeros((batch, s_max, cfg.qk_rope_dim), cdt),
            length=jnp.zeros((batch,), jnp.int32))
    if kind == "rec":
        return RecurrentCache(
            conv=jnp.zeros((batch, cfg.conv_kernel - 1, cfg.lru_width), cdt),
            h=jnp.zeros((batch, cfg.lru_width), jnp.float32))
    if kind in ("mamba", "mamba_moe"):
        d_in, H, G, N, P, conv_ch = _dims(cfg)
        return SSMCache(
            conv=jnp.zeros((batch, cfg.conv_kernel - 1, conv_ch), cdt),
            h=jnp.zeros((batch, H, P, N), jnp.float32))
    if kind == "dec":
        KV, hd = cfg.n_kv_heads, cfg.head_dim
        return {
            "self": KVCache(k=jnp.zeros((batch, s_max, KV, hd), cdt),
                            v=jnp.zeros((batch, s_max, KV, hd), cdt),
                            length=jnp.zeros((batch,), jnp.int32)),
            "cross_k": jnp.zeros((batch, enc_seq, KV, hd), cdt),
            "cross_v": jnp.zeros((batch, enc_seq, KV, hd), cdt),
        }
    raise KeyError(kind)


def cache_axes(kind: str, cfg):
    """Logical sharding axes mirroring :func:`init_cache`'s structure."""
    kv = ("batch", "kv_seq", "kv_heads", None)
    if kind in ("dense", "moe", "enc", "lattn", "attn_moe"):
        return KVCache(k=kv, v=kv, length=("batch",))
    if kind in ("mla_dense", "mla_moe"):
        return MLACache(ckv=("batch", "kv_seq", None),
                        krope=("batch", "kv_seq", None), length=("batch",))
    if kind == "rec":
        return RecurrentCache(conv=("batch", None, "lru"), h=("batch", "lru"))
    if kind in ("mamba", "mamba_moe"):
        return SSMCache(conv=("batch", None, "ff"),
                        h=("batch", "ssm_heads", None, None))
    if kind == "dec":
        return {"self": KVCache(k=kv, v=kv, length=("batch",)),
                "cross_k": ("batch", None, "kv_heads", None),
                "cross_v": ("batch", None, "kv_heads", None)}
    raise KeyError(kind)


def _cross_attention(x, p, cfg, ck, cv):
    """Decoder cross-attention against precomputed encoder K/V."""
    import math

    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    dt = x.dtype
    q = (x @ p["wq"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    q = q.reshape(B, S, H, hd)
    from .layers import _sdpa

    mask = jnp.ones((B, S, ck.shape[1]), bool)
    y = _sdpa(q, ck, cv, mask)
    y = y.reshape(B, S, H * hd) @ p["wo"].astype(dt)
    return y


def cross_kv(x_enc, p, cfg):
    """Precompute encoder K/V for a decoder block's cross-attention."""
    B, S, D = x_enc.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    dt = x_enc.dtype
    k = x_enc @ p["wk"].astype(dt)
    v = x_enc @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return k.reshape(B, S, KV, hd), v.reshape(B, S, KV, hd)


def apply_block(kind: str, x, p, cfg, positions, cache=None, layer=None):
    """Returns (x_out, new_cache, aux_loss).  With ``layer``, ``cache``
    stacks a scanned group's caches and the block updates entry ``layer``
    (``layers.layer_view``): a decode step's rows and states, in place."""
    zero = jnp.zeros((), jnp.float32)
    if kind in ("dense", "moe"):
        h, c = attention(_norm(x, p["ln1"], cfg), p["attn"], cfg, positions,
                         cache=cache, layer=layer)
        x = x + h
        if kind == "dense":
            x = x + mlp(_norm(x, p["ln2"], cfg), p["mlp"], cfg)
            return x, c, zero
        y, aux = moe_ffn(_norm(x, p["ln2"], cfg), p["moe"], cfg)
        return x + y, c, aux
    if kind in ("mla_dense", "mla_moe"):
        h, c = mla_attention(_norm(x, p["ln1"], cfg), p["attn"], cfg, positions,
                             cache=cache, layer=layer)
        x = x + h
        if kind == "mla_dense":
            x = x + mlp(_norm(x, p["ln2"], cfg), p["mlp"], cfg)
            return x, c, zero
        y, aux = moe_ffn(_norm(x, p["ln2"], cfg), p["moe"], cfg)
        return x + y, c, aux
    if kind == "lattn":
        h, c = attention(_norm(x, p["ln1"], cfg), p["attn"], cfg, positions,
                         window=cfg.window, cache=cache, layer=layer)
        x = x + h
        x = x + mlp(_norm(x, p["ln2"], cfg), p["mlp"], cfg)
        return x, c, zero
    if kind == "rec":
        h, c = recurrent_block(_norm(x, p["ln1"], cfg), p["rec"], cfg,
                               cache=cache, layer=layer)
        x = x + h
        x = x + mlp(_norm(x, p["ln2"], cfg), p["mlp"], cfg)
        return x, c, zero
    if kind == "mamba":
        h, c = mamba_block(_norm(x, p["ln"], cfg), p["mixer"], cfg,
                           cache=cache, layer=layer)
        return x + h, c, zero
    if kind in ("mamba_moe", "attn_moe"):
        xn = _norm(x, p["ln1"], cfg)
        if kind == "mamba_moe":
            h, c = mamba_block(xn, p["mixer"], cfg, cache=cache, layer=layer)
        else:
            h, c = attention(xn, p["attn"], cfg, positions, cache=cache,
                             layer=layer)
        x = x + h * cfg.residual_multiplier
        y, rows = expert_ffn(_norm(x, p["ln2"], cfg), p["moe"], cfg)
        return x + y * cfg.residual_multiplier, c, {"expert_rows": rows}
    if kind == "enc":
        # bidirectional self-attention (no mask, no rope — whisper uses
        # absolute sinusoidal positions added at the embedding)
        from .layers import _qkv, _sdpa

        B, S, _ = x.shape
        xn = _norm(x, p["ln1"], cfg)
        q, k, v = _qkv(xn, p["attn"], cfg)
        y = _sdpa(q, k, v, jnp.ones((B, S, S), bool))
        y = y.reshape(B, S, -1) @ p["attn"]["wo"].astype(x.dtype)
        x = x + y
        x = x + mlp(_norm(x, p["ln2"], cfg), p["mlp"], cfg)
        return x, None, zero
    if kind == "dec":
        sc = cache.get("self") if cache is not None else None
        h, new_self = attention(_norm(x, p["ln1"], cfg), p["attn"], cfg,
                                positions, cache=sc, layer=layer)
        x = x + h
        # the encoder's K/V are read, never written
        ck, cv = layer_view((cache["cross_k"], cache["cross_v"]), layer)
        x = x + _cross_attention(_norm(x, p["lnx"], cfg), p["xattn"], cfg, ck, cv)
        x = x + mlp(_norm(x, p["ln2"], cfg), p["mlp"], cfg)
        new_cache = {"self": new_self, "cross_k": cache["cross_k"],
                     "cross_v": cache["cross_v"]}
        return x, new_cache, zero
    raise KeyError(kind)
