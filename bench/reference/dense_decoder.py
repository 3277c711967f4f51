"""Plain float32 forward of a dense pre-norm decoder: InternLM2
(arXiv:2403.17297) and its kin.

Each layer: RMSNorm, grouped-query attention with rotary positions
(rotate-half convention, ``rope_theta``), a residual add, RMSNorm, a SwiGLU
MLP, a residual add; then a final RMSNorm and the output head.  Everything
is float32 under ``precision=HIGHEST`` and computed with no cache and no
batching tricks: one causal forward over each whole sequence, layer by
layer, in blocks of queries so that it fits beside nothing else on a chip.

Departures from the paper, none of which a run of this benchmark reaches:
InternLM2's dynamic NTK scaling of the rotary base acts only past its
32,768 trained positions, and is left out; no bias anywhere, as in the
released configuration.

The weights are the benchmark's own, made here from the seed, and
:func:`program_params` hands the same numbers to the program in its layout,
so the reference never reads anything the program made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common

HI = jax.lax.Precision.HIGHEST


def sizes(spec: dict):
    d = spec["hidden_size"]
    h = spec["num_attention_heads"]
    kv = spec["num_key_value_heads"]
    hd = spec.get("head_dim") or d // h
    return d, h, kv, hd, spec["intermediate_size"]


# ---------------------------------------------------------------- weights --


def layer_weights(spec: dict, key) -> dict:
    d, h, kv, hd, f = sizes(spec)
    k = jax.random.split(key, 9)
    n = common.normal
    return {
        "attn_norm": 1.0 + 0.1 * n(k[0], (d,)),
        "wq": n(k[1], (d, h * hd)) / math.sqrt(d),
        "wk": n(k[2], (d, kv * hd)) / math.sqrt(d),
        "wv": n(k[3], (d, kv * hd)) / math.sqrt(d),
        "wo": n(k[4], (h * hd, d)) / math.sqrt(h * hd),
        "mlp_norm": 1.0 + 0.1 * n(k[5], (d,)),
        "w_gate": n(k[6], (d, f)) / math.sqrt(d),
        "w_up": n(k[7], (d, f)) / math.sqrt(d),
        "w_down": n(k[8], (f, d)) / math.sqrt(f),
    }


def outer_weights(spec: dict, key) -> dict:
    d, v = spec["hidden_size"], spec["vocab_size"]
    k = jax.random.split(key, 3)
    w = {"embed": 0.02 * common.normal(k[0], (v, d)),
         "final_norm": 1.0 + 0.1 * common.normal(k[1], (d,))}
    if not spec["tie_word_embeddings"]:
        w["head"] = common.normal(k[2], (d, v)) / math.sqrt(d)
    return w


def program_config(spec: dict) -> dict:
    """The program's configuration fields for these sizes."""
    d, h, kv, hd, f = sizes(spec)
    return dict(family="dense", n_layers=spec["num_hidden_layers"],
                d_model=d, n_heads=h, n_kv_heads=kv, head_dim=hd, d_ff=f,
                vocab_size=spec["vocab_size"], rope_theta=spec["rope_theta"],
                norm_eps=spec["rms_norm_eps"],
                tie_embeddings=spec["tie_word_embeddings"], act="silu",
                qkv_bias=False)


def program_params(spec: dict, key, dtype) -> dict:
    """The same weights in the program's layout: layers stacked on a
    leading axis, stored in ``dtype``.  Jittable."""
    o = outer_weights(spec, common.outer_key(key))
    lw = jax.vmap(lambda i: layer_weights(spec, common.layer_key(key, i)))(
        jnp.arange(spec["num_hidden_layers"]))
    block = {"ln1": {"w": lw["attn_norm"]},
             "attn": {"wq": lw["wq"], "wk": lw["wk"], "wv": lw["wv"],
                      "wo": lw["wo"]},
             "ln2": {"w": lw["mlp_norm"]},
             "mlp": {"gate": lw["w_gate"], "up": lw["w_up"],
                     "down": lw["w_down"]}}
    p = {"embed": o["embed"], "final_norm": {"w": o["final_norm"]},
         "stacks": [{"b0": block}]}
    if "head" in o:
        p["head"] = o["head"]
    return jax.tree.map(lambda t: t.astype(dtype), p)


# ---------------------------------------------------------------- forward --


def _rope(x, theta):
    """x: (n, S, heads, hd) at positions 0..S-1."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(spec, w, x, mode, q_block):
    d, h, kv, hd, f = sizes(spec)
    eps = spec["rms_norm_eps"]
    mm = common.matmul(mode)
    n, s, _ = x.shape
    a = common.rmsnorm(x, w["attn_norm"], eps)
    q = _rope(mm(a, w["wq"]).reshape(n, s, h, hd), spec["rope_theta"])
    k = _rope(mm(a, w["wk"]).reshape(n, s, kv, hd), spec["rope_theta"])
    v = mm(a, w["wv"]).reshape(n, s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)            # query head i reads kv i//g
    v = jnp.repeat(v, h // kv, axis=2)
    outs = []
    for lo in range(0, s, q_block):
        qb = q[:, lo:lo + q_block]
        hi = lo + qb.shape[1]
        sc = jnp.einsum("nqhd,nkhd->nhqk", qb, k[:, :hi],
                        precision=HI) / math.sqrt(hd)
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        outs.append(jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, -1),
                               v[:, :hi], precision=HI))
    o = jnp.concatenate(outs, axis=1).reshape(n, s, h * hd)
    x = x + mm(o, w["wo"])
    m = common.rmsnorm(x, w["mlp_norm"], eps)
    return x + mm(jax.nn.silu(mm(m, w["w_gate"])) * mm(m, w["w_up"]),
                  w["w_down"])


def logits(spec: dict, key, tokens, read, mode: str = "f32",
           q_block: int = 512):
    """Logits at positions ``read`` (n, k) of each sequence of ``tokens``
    (n, S), the sequences' own causal forward with no cache.  ``mode``
    "f32" is the reference; "fp8" rounds every matrix product's operands
    to float8 e4m3 (per-tensor scale), the control."""
    o = jax.jit(lambda k: outer_weights(spec, k))(common.outer_key(key))
    x = jnp.take(o["embed"], tokens, axis=0)
    step = jax.jit(lambda x, i: _layer(
        spec, layer_weights(spec, common.layer_key(key, i)), x, mode,
        q_block))
    for i in range(spec["num_hidden_layers"]):
        x = step(x, i)
    head = o["embed"].T if spec["tie_word_embeddings"] else o["head"]
    return common.head_logits(x, read, o["final_norm"], head,
                              spec["rms_norm_eps"], mode)
