"""Plain float32 forward of Mamba-2 (arXiv:2405.21060), attention-free.

Each layer: RMSNorm, then the Mamba-2 mixer and a residual add.  The mixer
projects to (z, xBC, dt); runs a causal depthwise convolution over xBC with
its bias and a SiLU; splits x, B, C; sets dt = softplus(dt + dt_bias) and
A = -exp(A_log); runs the recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t
B_t^T one position at a time, reads y_t = h_t C_t + D x_t, gates it with
SiLU(z) before a RMSNorm over the inner width, and projects back.  A final
RMSNorm and the head (the embedding, transposed, when tied) give the
logits.  Everything is float32 under ``precision=HIGHEST``: the sequential
recurrence, not the chunked state-space-duality algorithm the program runs,
and no cache.

Departure from the released model: its residual stream is float32
(``residual_in_fp32``); here everything is, so nothing is lost.

The weights are the benchmark's own, made here from the seed, with the
paper's initialisation of A and of dt's bias; :func:`program_params` hands
the same numbers to the program in its layout.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common


def sizes(spec: dict):
    d = spec["hidden_size"]
    d_in = spec["expand"] * d
    p = spec["head_dim"]
    h = d_in // p
    g, n = spec["n_groups"], spec["state_size"]
    return d, d_in, h, g, n, p, d_in + 2 * g * n


# ---------------------------------------------------------------- weights --


def layer_weights(spec: dict, key) -> dict:
    d, d_in, h, g, n, p, conv_ch = sizes(spec)
    kc = spec["conv_kernel"]
    k = jax.random.split(key, 10)
    nrm = common.normal
    dt = jnp.exp(jax.random.uniform(k[5], (h,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "norm": 1.0 + 0.1 * nrm(k[0], (d,)),
        "in_proj": nrm(k[1], (d, 2 * d_in + 2 * g * n + h)) / math.sqrt(d),
        "conv_w": nrm(k[2], (kc, conv_ch)) / math.sqrt(kc),
        "conv_b": 0.1 * nrm(k[3], (conv_ch,)),
        "a_log": jnp.log(jax.random.uniform(k[4], (h,), jnp.float32, 1, 16)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus⁻¹(dt)
        "d_skip": 1.0 + 0.1 * nrm(k[6], (h,)),
        "gate_norm": 1.0 + 0.1 * nrm(k[7], (d_in,)),
        "out_proj": nrm(k[8], (d_in, d)) / math.sqrt(d_in),
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
    return dict(family="ssm", n_layers=spec["num_hidden_layers"],
                d_model=spec["hidden_size"], vocab_size=spec["vocab_size"],
                ssm_state=spec["state_size"], ssm_expand=spec["expand"],
                ssm_headdim=spec["head_dim"], ssm_ngroups=spec["n_groups"],
                conv_kernel=spec["conv_kernel"], ssd_chunk=spec["chunk_size"],
                norm_eps=spec["layer_norm_epsilon"],
                tie_embeddings=spec["tie_word_embeddings"])


def program_params(spec: dict, key, dtype) -> dict:
    """The same weights in the program's layout: layers stacked on a
    leading axis, stored in ``dtype``.  Jittable."""
    o = outer_weights(spec, common.outer_key(key))
    lw = jax.vmap(lambda i: layer_weights(spec, common.layer_key(key, i)))(
        jnp.arange(spec["num_hidden_layers"]))
    mixer = {"in_proj": lw["in_proj"], "conv_w": lw["conv_w"],
             "conv_b": lw["conv_b"], "a_log": lw["a_log"],
             "d_skip": lw["d_skip"], "dt_bias": lw["dt_bias"],
             "norm_w": lw["gate_norm"], "out_proj": lw["out_proj"]}
    p = {"embed": o["embed"], "final_norm": {"w": o["final_norm"]},
         "stacks": [{"b0": {"ln": {"w": lw["norm"]}, "mixer": mixer}}]}
    if "head" in o:
        p["head"] = o["head"]
    return jax.tree.map(lambda t: t.astype(dtype), p)


# ---------------------------------------------------------------- forward --


def _layer(spec, w, x, mode):
    d, d_in, h, g, n, p, conv_ch = sizes(spec)
    kc = spec["conv_kernel"]
    eps = spec["layer_norm_epsilon"]
    mm = common.matmul(mode)
    b, s, _ = x.shape
    u = mm(common.rmsnorm(x, w["norm"], eps), w["in_proj"])
    z, xbc, dt = jnp.split(u, [d_in, 2 * d_in + 2 * g * n], axis=-1)
    xp = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    xbc = sum(xp[:, i:i + s] * w["conv_w"][i] for i in range(kc))
    xbc = jax.nn.silu(xbc + w["conv_b"])
    xs, bm, cm = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
    xs = xs.reshape(b, s, h, p)
    heads_per_group = h // g
    bm = jnp.repeat(bm.reshape(b, s, g, n), heads_per_group, axis=2)
    cm = jnp.repeat(cm.reshape(b, s, g, n), heads_per_group, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])                     # (b, s, h)
    a = -jnp.exp(w["a_log"])

    def step(state, t):
        xt, bt, ct, dtt = t
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct,
                                 precision=common.HI)

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt))
    _, y = jax.lax.scan(step, h0, seq)
    y = jnp.moveaxis(y, 0, 1) + xs * w["d_skip"][:, None]
    y = common.rmsnorm(y.reshape(b, s, d_in) * jax.nn.silu(z),
                       w["gate_norm"], eps)
    return x + mm(y, w["out_proj"])


def logits(spec: dict, key, tokens, read, mode: str = "f32"):
    """Logits at positions ``read`` (n, k) of each sequence of ``tokens``
    (n, S); ``mode`` as in :func:`.common.matmul`."""
    o = jax.jit(lambda k: outer_weights(spec, k))(common.outer_key(key))
    x = jnp.take(o["embed"], tokens, axis=0)
    step = jax.jit(lambda x, i: _layer(
        spec, layer_weights(spec, common.layer_key(key, i)), x, mode))
    for i in range(spec["num_hidden_layers"]):
        x = step(x, i)
    head = o["embed"].T if spec["tie_word_embeddings"] else o["head"]
    return common.head_logits(x, read, o["final_norm"], head,
                              spec["layer_norm_epsilon"], mode)
