"""Plain float32 forward of Granite 4.0-H (``granitemoehybrid``): Mamba-2
and grouped-query attention layers, each followed by a mixture of experts,
on the share of the experts that one chip holds.

Each layer: RMSNorm, the layer's mixer, its output times
``residual_multiplier`` added to the residual; RMSNorm, the expert FFN, its
output times ``residual_multiplier`` added.  The mixer is the one
``layer_types`` names:

- ``mamba``: the Mamba-2 mixer of :mod:`.mamba2` (conv with bias, the
  sequential recurrence one position at a time, a gated RMSNorm over the
  whole inner width);
- ``attention``: grouped-query attention with no positions
  (``position_embedding_type`` "nope"), scores times
  ``attention_multiplier``, causal.

The expert FFN: the router scores all ``router_experts`` experts; each
token keeps its top ``num_experts_per_tok``, weighted by a softmax over the
chosen logits; the experts held here, the first ``num_local_experts``, add
their SwiGLU outputs (width ``intermediate_size``) times those weights, and
what the absent experts would add is left out, as on the chip the program
stands for; the shared SwiGLU MLP (``shared_intermediate_size``) is added
whole.  Embeddings are multiplied by ``embedding_multiplier``; a final
RMSNorm, the head (the embedding, transposed) and a division by
``logits_scaling`` give the logits.  Everything is float32 under
``precision=HIGHEST``, with no cache; rows and positions are padded
only where nothing real reads them (:func:`logits`).

Departures, none of which changes the arithmetic beyond float32 rounding:
a Mamba layer's residual multiplier is folded into its ``out_proj`` (the
mixer of :mod:`.mamba2` adds its own output to the residual); the router's
auxiliary loss, a training term, is left out.  The released model stores
its weights in bfloat16; these are the float32 values the program's
bfloat16 weights are rounded from.

The weights are the benchmark's own, made here from the seed: the Mamba
mixers as :mod:`.mamba2` makes them, expert ``e`` of a layer from a key of
its own, so a share holds the same experts whatever its size, and the
embedding at 0.02 / ``embedding_multiplier``.
:func:`program_params` hands the same numbers to the program in its layout.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common, mamba2

HI = common.HI


def _check(spec: dict) -> None:
    """The variant of the family this reference writes down."""
    want = {"position_embedding_type": "nope", "tie_word_embeddings": True,
            "attention_bias": False, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "hidden_act": "silu"}
    other = {k: spec[k] for k, v in want.items() if spec[k] != v}
    if other:
        raise ValueError(f"granite_hybrid models {want}; the file gives {other}")


def mamba_spec(spec: dict) -> dict:
    """The Mamba-2 sizes in :mod:`.mamba2`'s names."""
    m = {"hidden_size": spec["hidden_size"], "expand": spec["mamba_expand"],
         "head_dim": spec["mamba_d_head"], "n_groups": spec["mamba_n_groups"],
         "state_size": spec["mamba_d_state"],
         "conv_kernel": spec["mamba_d_conv"],
         "layer_norm_epsilon": spec["rms_norm_eps"]}
    heads = mamba2.sizes(m)[2]
    if heads != spec["mamba_n_heads"]:
        raise ValueError(f"mamba_n_heads {spec['mamba_n_heads']} but expand × "
                         f"hidden / head = {heads}")
    return m


def layer_types(spec: dict) -> list[str]:
    return list(spec["layer_types"][:spec["num_hidden_layers"]])


# ---------------------------------------------------------------- weights --


def attention_weights(spec: dict, key) -> dict:
    d, h, kv = (spec["hidden_size"], spec["num_attention_heads"],
                spec["num_key_value_heads"])
    hd = d // h
    k = jax.random.split(key, 5)
    n = common.normal
    return {"norm": 1.0 + 0.1 * n(k[0], (d,)),
            "wq": n(k[1], (d, h * hd)) / math.sqrt(d),
            "wk": n(k[2], (d, kv * hd)) / math.sqrt(d),
            "wv": n(k[3], (d, kv * hd)) / math.sqrt(d),
            "wo": n(k[4], (h * hd, d)) / math.sqrt(h * hd)}


def _swiglu_weights(key, d: int, f: int) -> dict:
    k = jax.random.split(key, 3)
    n = common.normal
    return {"gate": n(k[0], (d, f)) / math.sqrt(d),
            "up": n(k[1], (d, f)) / math.sqrt(d),
            "down": n(k[2], (f, d)) / math.sqrt(f)}


def expert_weights(spec: dict, key) -> dict:
    """The FFN's norm, router, held experts (stacked) and shared MLP."""
    d = spec["hidden_size"]
    k = jax.random.split(key, 4)
    experts = jax.vmap(lambda e: _swiglu_weights(
        jax.random.fold_in(k[2], e), d, spec["intermediate_size"]))(
        jnp.arange(spec["num_local_experts"]))
    return {"norm": 1.0 + 0.1 * common.normal(k[0], (d,)),
            "router": common.normal(k[1], (d, spec["router_experts"]))
            / math.sqrt(d),
            "experts": experts,
            "shared": _swiglu_weights(k[3], d,
                                      spec["shared_intermediate_size"])}


def layer_weights(spec: dict, kind: str, key) -> dict:
    """Layer weights: ``mixer`` (Mamba-2 or attention) and ``ffn``."""
    k = jax.random.split(key, 2)
    mixer = (mamba2.layer_weights(mamba_spec(spec), k[0]) if kind == "mamba"
             else attention_weights(spec, k[0]))
    return {"mixer": mixer, "ffn": expert_weights(spec, k[1])}


def outer_weights(spec: dict, key) -> dict:
    d, v = spec["hidden_size"], spec["vocab_size"]
    k = jax.random.split(key, 2)
    # the embedding times embedding_multiplier has the other references'
    # scale, 0.02: at 0.02 before the multiplier the tied head would read
    # back each position's own token, far above every other, whatever
    # the layers add
    std = 0.02 / spec["embedding_multiplier"]
    return {"embed": std * common.normal(k[0], (v, d)),
            "final_norm": 1.0 + 0.1 * common.normal(k[1], (d,))}


def program_config(spec: dict) -> dict:
    """The program's configuration fields for these sizes."""
    _check(spec)
    mamba_spec(spec)
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    n = spec["num_hidden_layers"]
    return dict(
        family="hybrid", n_layers=n, d_model=d, n_heads=h,
        n_kv_heads=spec["num_key_value_heads"], head_dim=d // h, d_ff=0,
        vocab_size=spec["vocab_size"], norm_eps=spec["rms_norm_eps"],
        tie_embeddings=True, act="silu", qkv_bias=False,
        layer_types=tuple(layer_types(spec)), position_embedding="nope",
        rope_theta=float(spec["rope_theta"]),
        embedding_multiplier=float(spec["embedding_multiplier"]),
        residual_multiplier=float(spec["residual_multiplier"]),
        attention_multiplier=float(spec["attention_multiplier"]),
        logits_scaling=float(spec["logits_scaling"]),
        n_experts=spec["router_experts"],
        n_experts_held=spec["num_local_experts"],
        top_k=spec["num_experts_per_tok"], moe_d_ff=spec["intermediate_size"],
        n_shared_experts=1, shared_d_ff=spec["shared_intermediate_size"],
        ssm_state=spec["mamba_d_state"], ssm_expand=spec["mamba_expand"],
        ssm_headdim=spec["mamba_d_head"], ssm_ngroups=spec["mamba_n_groups"],
        conv_kernel=spec["mamba_d_conv"], ssd_chunk=spec["mamba_chunk_size"])


def _program_block(kind: str, w: dict) -> dict:
    """One layer's weights under the program's names."""
    m, f = w["mixer"], w["ffn"]
    if kind == "mamba":
        mixer = ("mixer", {"in_proj": m["in_proj"], "conv_w": m["conv_w"],
                           "conv_b": m["conv_b"], "a_log": m["a_log"],
                           "d_skip": m["d_skip"], "dt_bias": m["dt_bias"],
                           "norm_w": m["gate_norm"],
                           "out_proj": m["out_proj"]})
    else:
        mixer = ("attn", {k: m[k] for k in ("wq", "wk", "wv", "wo")})
    return {"ln1": {"w": m["norm"]}, mixer[0]: mixer[1],
            "ln2": {"w": f["norm"]},
            "moe": {"router": f["router"], "experts": f["experts"],
                    "shared": f["shared"]}}


def _period(types: list[str]) -> int:
    """The shortest period that tiles the layer pattern, as the program
    groups its layers."""
    n = len(types)
    return next(p for p in range(1, n + 1) if n % p == 0
                and all(types[i] == types[i % p] for i in range(n)))


def program_params(spec: dict, key, dtype) -> dict:
    """The same weights in the program's layout, each layer cast to
    ``dtype`` as it is made: one period of the pattern, its blocks
    ``b0``, ``b1``, … stacked over the repeats where there are more than
    one.  Jittable."""
    types = layer_types(spec)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    blocks = [cast(_program_block(t, layer_weights(
        spec, t, common.layer_key(key, i)))) for i, t in enumerate(types)]
    p = _period(types)
    reps = len(types) // p
    group = {f"b{j}": (blocks[j] if reps == 1 else jax.tree.map(
        lambda *xs: jnp.stack(xs), *blocks[j::p])) for j in range(p)}
    o = outer_weights(spec, common.outer_key(key))
    return {"embed": o["embed"].astype(dtype),
            "final_norm": {"w": o["final_norm"].astype(dtype)},
            "stacks": [group]}


# ---------------------------------------------------------------- forward --


def _attention(spec, w, x, mm):
    d, h, kv = (spec["hidden_size"], spec["num_attention_heads"],
                spec["num_key_value_heads"])
    hd = d // h
    n, s, _ = x.shape
    a = common.rmsnorm(x, w["norm"], spec["rms_norm_eps"])
    q = mm(a, w["wq"]).reshape(n, s, h, hd)
    k = jnp.repeat(mm(a, w["wk"]).reshape(n, s, kv, hd), h // kv, axis=2)
    v = jnp.repeat(mm(a, w["wv"]).reshape(n, s, kv, hd), h // kv, axis=2)
    sc = jnp.einsum("nqhd,nkhd->nhqk", q, k,
                    precision=HI) * spec["attention_multiplier"]
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(n, s, h * hd)
    return mm(o, w["wo"])


def _swiglu(w, x, mm):
    return mm(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]), w["down"])


def _experts(spec, w, x, mm):
    m = common.rmsnorm(x, w["norm"], spec["rms_norm_eps"])
    top_l, top_i = jax.lax.top_k(mm(m, w["router"]),
                                 spec["num_experts_per_tok"])
    g = jax.nn.softmax(top_l, axis=-1)                        # (n, s, k)
    out = _swiglu(w["shared"], m, mm)
    for e in range(spec["num_local_experts"]):
        gate = jnp.sum(jnp.where(top_i == e, g, 0.0), -1)     # (n, s)
        we = jax.tree.map(lambda t: t[e], w["experts"])
        out = out + gate[..., None] * _swiglu(we, m, mm)
    return out


def _mixer(spec, kind, mode, w, x):
    """``x`` plus the layer's mixer, its output times
    ``residual_multiplier``."""
    rm = spec["residual_multiplier"]
    if kind == "mamba":
        mixer = dict(w, out_proj=w["out_proj"] * rm)
        return mamba2._layer(mamba_spec(spec), mixer, x, mode)
    return x + rm * _attention(spec, w, x, common.matmul(mode))


def _ffn(spec, mode, w, x):
    """``x`` plus the expert FFN, its output times
    ``residual_multiplier``."""
    return x + spec["residual_multiplier"] * _experts(
        spec, w, x, common.matmul(mode))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def logits(spec: dict, key, tokens, read, mode: str = "f32"):
    """Logits at positions ``read`` (n, k) of each sequence of ``tokens``
    (n, S), the sequences' own causal forward with no cache.  ``mode``
    "f32" is the reference; "fp8" rounds every matrix product's operands
    to float8 e4m3 (per-tensor scale), the control.

    Rows are padded to a multiple of 8 and positions to a multiple of 128
    (the padding is never read: the forward is causal and row by row), and
    a layer's weights, mixer and expert FFN are separate programs, so a run
    compiles a few shapes whose compiles the persistent cache keeps for
    the next run."""
    _check(spec)
    (n, s), k = tokens.shape, read.shape[1]
    rows = _round_up(n, 8)
    tokens = jnp.pad(tokens, ((0, rows - n), (0, _round_up(s, 128) - s)))
    read = jnp.pad(read, ((0, rows - n), (0, _round_up(k, 128) - k)))
    o = jax.jit(lambda k: outer_weights(spec, k))(common.outer_key(key))
    x = jnp.take(o["embed"], tokens, axis=0) * spec["embedding_multiplier"]
    kinds = set(layer_types(spec))
    weights = {t: jax.jit(lambda i, k, _t=t: layer_weights(
        spec, _t, common.layer_key(k, i))) for t in kinds}
    mixers = {t: jax.jit(lambda w, x, _t=t: _mixer(spec, _t, mode, w, x))
              for t in kinds}
    ffn = jax.jit(lambda w, x: _ffn(spec, mode, w, x))
    for i, t in enumerate(layer_types(spec)):
        w = weights[t](i, key)
        x = ffn(w["ffn"], mixers[t](w["mixer"], x))
        del w
    return common.head_logits(x, read, o["final_norm"], o["embed"].T,
                              spec["rms_norm_eps"], mode)[:n, :k] \
        / spec["logits_scaling"]
