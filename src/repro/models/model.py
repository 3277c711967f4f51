"""Model factory: builds every assigned architecture from its ModelConfig.

One uniform interface for the launcher, trainer, server and dry-run:

    m = build_model(cfg)
    params = m.init(key)                        # concrete (smoke/train)
    specs  = jax.eval_shape(m.init, key)        # dry-run param ShapeDtypeStructs
    loss, aux = m.loss(params, batch)
    logits, caches = m.prefill(params, batch)
    caches = m.init_caches(B, S_max, filled=S)  # serving state
    logits, caches = m.decode_step(params, tokens, caches, pos)
    # with a share of the experts held here (m.expert_slots > 0):
    logits, caches, expert_rows = m.decode_step(params, tokens, caches, pos)

Layer stacks are grouped into homogeneous runs; each run of length >1 is
``lax.scan``-ned when ``cfg.scan_layers`` (compile time stays flat in depth)
with optional ``jax.checkpoint`` rematerialisation.  Heterogeneous patterns
(RecurrentGemma's rec-rec-attn, Granite's per-layer ``layer_types``) scan
over the repeating *period*.  Training and prefill build a scanned group's
caches as the scan's outputs; a decode step carries them through its scan
and writes only what it changes into them (``m.inplace_layers`` counts the
layers it so updates).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import sharding as sh
from .blocks import (apply_block, block_axes, cache_axes as _block_cache_axes,
                     cross_kv, init_block, init_cache)
from .layers import dense_init, layernorm, rmsnorm

Pytree = Any


#: block kind of each of ``cfg.layer_types``' layer types
LAYER_TYPE_KINDS = {"mamba": "mamba_moe", "attention": "attn_moe"}


def _shortest_period(kinds: tuple[str, ...]) -> list[tuple[tuple[str, ...], int]]:
    """[(period, repeat)] for the shortest period that tiles ``kinds``."""
    n = len(kinds)
    p = next(p for p in range(1, n + 1) if n % p == 0
             and all(kinds[i] == kinds[i % p] for i in range(n)))
    return [(kinds[:p], n // p)]


def layer_groups(cfg: ModelConfig) -> list[tuple[tuple[str, ...], int]]:
    """List of (period_kinds, repeat).  A period of one kind is the common
    case; RecurrentGemma uses the repeating period ("rec","rec","lattn");
    a per-layer ``layer_types`` pattern is grouped into its shortest
    period (Granite 4.0-H: nine Mamba-2 layers and one attention layer)."""
    if cfg.layer_types:
        if len(cfg.layer_types) < cfg.n_layers:
            raise ValueError(f"{len(cfg.layer_types)} layer_types for "
                             f"{cfg.n_layers} layers")
        return _shortest_period(tuple(
            LAYER_TYPE_KINDS[t] for t in cfg.layer_types[:cfg.n_layers]))
    if cfg.family == "ssm":
        return [(("mamba",), cfg.n_layers)]
    if cfg.family == "hybrid":
        pat = tuple("lattn" if k == "attn" else k for k in cfg.block_pattern)
        reps = cfg.n_layers // len(pat)
        out: list[tuple[tuple[str, ...], int]] = [(pat, reps)]
        tail = cfg.n_layers - reps * len(pat)
        if tail:
            out.append((pat[:tail], 1))
        return out
    if cfg.family == "moe":
        attn = "mla_dense" if cfg.use_mla else "dense"
        moe = "mla_moe" if cfg.use_mla else "moe"
        out = []
        if cfg.n_dense_layers:
            out.append(((attn,), cfg.n_dense_layers))
        out.append(((moe,), cfg.n_layers - cfg.n_dense_layers))
        return out
    if cfg.family == "audio":
        return [(("dec",), cfg.n_layers)]          # encoder handled separately
    return [(("dense",), cfg.n_layers)]            # dense / vlm


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    axes: Callable
    loss: Callable          # (params, batch) -> (loss, aux)
    prefill: Callable       # (params, batch) -> (last logits, caches)
    decode_step: Callable   # (params, tokens(B,1), caches, pos(B,)) -> (logits, caches)
                            # [, expert_rows] where expert_slots
    init_caches: Callable   # (batch, s_max, filled=0) -> caches
    cache_axes: Callable    # () -> logical axes tree mirroring init_caches
    expert_slots: int = 0   # layers × experts held, where fewer are held
                            # than routed: decode_step also returns rows
    inplace_layers: int = 0  # layers whose decode caches the step updates
                             # inside their scanned group's stacked buffers


def _sinusoidal(positions, d):
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(10000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _stack_pytrees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def build_model(cfg: ModelConfig) -> Model:
    groups = layer_groups(cfg)
    cdt = jnp.dtype(cfg.dtype)
    pdt = jnp.dtype(cfg.param_dtype)
    share_layers = sum(reps * sum(k in LAYER_TYPE_KINDS.values() for k in period)
                       for period, reps in groups)
    held = cfg.n_experts_held
    expert_slots = share_layers * held if 0 < held < cfg.n_experts else 0

    def scanned(reps):
        return reps > 1 and cfg.scan_layers

    inplace_layers = sum(reps * len(period) for period, reps in groups
                         if scanned(reps))

    # ---------------------------------------------------------------- init --

    def init(key: jax.Array) -> Pytree:
        n_groups = len(groups)
        keys = jax.random.split(key, n_groups + 5)
        p: dict[str, Any] = {}
        p["embed"] = dense_init(keys[0], (cfg.vocab_size, cfg.d_model), pdt,
                                scale=0.02)
        p["final_norm"] = {"w": jnp.ones((cfg.d_model,), pdt)}
        if not cfg.tie_embeddings:
            p["head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab_size), pdt)
        stacks = []
        for g, (period, reps) in enumerate(groups):
            def one(k, _period=period):
                ks = jax.random.split(k, len(_period))
                return {f"b{i}": init_block(kind, ks[i], cfg)
                        for i, kind in enumerate(_period)}
            if reps == 1:
                stacks.append(one(keys[2 + g]))
            else:
                stacks.append(jax.vmap(one)(jax.random.split(keys[2 + g], reps)))
        p["stacks"] = stacks
        if cfg.family == "audio":
            ek = jax.random.split(keys[n_groups + 2], cfg.enc_layers)
            p["enc"] = jax.vmap(lambda k: init_block("enc", k, cfg))(ek)
            p["enc_norm"] = {"w": jnp.ones((cfg.d_model,), pdt),
                             "b": jnp.zeros((cfg.d_model,), pdt)}
        if cfg.mtp:
            kk = jax.random.split(keys[n_groups + 3], 2)
            p["mtp"] = {
                "proj": dense_init(kk[0], (2 * cfg.d_model, cfg.d_model), pdt),
                "block": init_block("mla_dense" if cfg.use_mla else "dense",
                                    kk[1], cfg),
                "norm": {"w": jnp.ones((cfg.d_model,), pdt)},
            }
        return p

    # ---------------------------------------------------------------- axes --

    def axes() -> Pytree:
        def _is_axes(x):
            return isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x)

        a: dict[str, Any] = {
            "embed": ("vocab", "fsdp"),
            "final_norm": {"w": ("embed",)},
        }
        if not cfg.tie_embeddings:
            a["head"] = ("fsdp", "vocab")
        stacks = []
        for period, reps in groups:
            blk = {f"b{i}": block_axes(kind, cfg)
                   for i, kind in enumerate(period)}
            if reps > 1:
                blk = jax.tree.map(lambda t: ("layers",) + t, blk,
                                   is_leaf=_is_axes)
            stacks.append(blk)
        a["stacks"] = stacks
        if cfg.family == "audio":
            enc = jax.tree.map(lambda t: ("layers",) + t, block_axes("enc", cfg),
                               is_leaf=_is_axes)
            a["enc"] = enc
            a["enc_norm"] = {"w": ("embed",), "b": ("embed",)}
        if cfg.mtp:
            a["mtp"] = {
                "proj": ("fsdp", None),
                "block": block_axes("mla_dense" if cfg.use_mla else "dense", cfg),
                "norm": {"w": ("embed",)},
            }
        return a

    # --------------------------------------------------------------- stacks --

    def _remat(fn):
        if cfg.remat == "none":
            return fn
        policy = None
        if cfg.remat == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)

    def aux_zero():
        z = {"router_loss": jnp.zeros((), jnp.float32)}
        if share_layers:
            z["expert_rows"] = jnp.zeros((), jnp.int32)
        return z

    def add_aux(total, a):
        """``total`` plus a block's aux: a load-balance loss, or counts."""
        a = a if isinstance(a, dict) else {"router_loss": a}
        return {k: t + a[k] if k in a else t for k, t in total.items()}

    def period_fn(period, positions):
        """A period's blocks in turn: (x, params, caches[, layer]) ->
        (x, new caches, aux)."""
        def fn(x, pp, pc, layer=None):
            aux = aux_zero()
            ncs = {}
            for i, kind in enumerate(period):
                c = pc[f"b{i}"] if pc is not None else None
                x, nc, a_ = apply_block(kind, x, pp[f"b{i}"], cfg, positions,
                                        cache=c, layer=layer)
                ncs[f"b{i}"] = nc
                aux = add_aux(aux, a_)
            return x, ncs, aux
        return fn

    def run_group(g, x, sp, gc, positions, aux_total):
        """One group of ``run_stacks``: (x, new caches, aux_total)."""
        period, reps = groups[g]
        fn = _remat(period_fn(period, positions))
        if reps == 1:
            x, ncs, a_ = fn(x, sp, gc)
            return x, ncs, add_aux(aux_total, a_)
        if scanned(reps):
            if gc is None:
                def body(carry, pp):
                    x, aux = carry
                    x, ncs, a_ = fn(x, pp, None)
                    return (x, add_aux(aux, a_)), ncs
                (x, aux_total), ncs = jax.lax.scan(body, (x, aux_total), sp)
            else:
                def body(carry, xs):
                    x, aux = carry
                    pp, pc = xs
                    x, ncs, a_ = fn(x, pp, pc)
                    return (x, add_aux(aux, a_)), ncs
                (x, aux_total), ncs = jax.lax.scan(
                    body, (x, aux_total), (sp, gc))
            return x, ncs, aux_total
        ncs_list = []
        for r in range(reps):
            pp = jax.tree.map(lambda t: t[r], sp)
            pc = jax.tree.map(lambda t: t[r], gc) if gc is not None else None
            x, ncs, a_ = fn(x, pp, pc)
            ncs_list.append(ncs)
            aux_total = add_aux(aux_total, a_)
        return x, _stack_pytrees(ncs_list), aux_total

    def run_stacks(x, stacks, positions, caches):
        """caches: list matching groups; entries may be None (no state needed,
        e.g. training without serving caches is handled by passing cross-KV
        only for audio).  Returns (x, new_caches, aux_total), aux_total a
        dict: "router_loss", and "expert_rows" where layers hold experts.
        A scanned group takes its caches as the scan's inputs and builds
        new ones as its outputs, which training's gradient needs."""
        aux_total = aux_zero()
        new_caches = []
        for g in range(len(groups)):
            gc = caches[g] if caches is not None else None
            x, ncs, aux_total = run_group(g, x, stacks[g], gc, positions,
                                          aux_total)
            new_caches.append(ncs)
        return x, new_caches, aux_total

    def decode_stacks(x, stacks, positions, caches):
        """Decode's layer loop, as ``run_stacks`` but for a scanned group:
        it carries the group's stacked caches through the scan, and each
        layer writes into them only what the step changes (a sequence
        cache its new row, a state its new value, a read-only cache
        nothing), so donated caches are updated in place and no cache is
        built anew.  No gradient runs here, so nothing is rematerialised."""
        aux_total = aux_zero()
        new_caches = []
        for g, (period, reps) in enumerate(groups):
            if not scanned(reps):
                x, gc, aux_total = run_group(g, x, stacks[g], caches[g],
                                             positions, aux_total)
                new_caches.append(gc)
                continue
            fn = period_fn(period, positions)

            def body(carry, xs, fn=fn):
                x, aux, gc = carry
                pp, layer = xs
                x, gc, a_ = fn(x, pp, gc, layer)
                return (x, add_aux(aux, a_), gc), None

            (x, aux_total, gc), _ = jax.lax.scan(
                body, (x, aux_total, caches[g]),
                (stacks[g], jnp.arange(reps, dtype=jnp.int32)))
            new_caches.append(gc)
        return x, new_caches, aux_total

    # ----------------------------------------------------- embedding / head --

    def embed_tokens(p, tokens, positions=None):
        x = jnp.take(p["embed"], tokens, axis=0).astype(cdt)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if not cfg.rope_theta:           # absolute sinusoidal positions
            if positions is None:
                positions = jnp.arange(tokens.shape[1])[None, :]
            x = x + _sinusoidal(positions, cfg.d_model).astype(cdt)
        return sh.constrain(x, "batch", "seq", "embed")

    def lm_logits(p, x):
        x = rmsnorm(x, p["final_norm"]["w"], cfg.norm_eps)
        head = p["embed"].T if cfg.tie_embeddings else p["head"]
        logits = x @ head.astype(cdt)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return sh.constrain(logits, "batch", "seq", "vocab")

    def xent(logits, targets, mask=None):
        # manual logsumexp keeping the exp in the compute dtype: avoids
        # materialising an f32 copy of the (B,S,V) logits
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        z = jnp.sum(jnp.exp(logits - m), axis=-1, dtype=jnp.float32)
        logz = jnp.log(z) + m[..., 0].astype(jnp.float32)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1)[..., 0].astype(jnp.float32)
        nll = logz - gold
        if mask is not None:
            return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return nll.mean()

    def encode_frames(p, frames):
        x = frames.astype(cdt)
        pos = jnp.arange(x.shape[1])[None, :]
        x = x + _sinusoidal(pos, cfg.d_model).astype(cdt)
        x = sh.constrain(x, "batch", "seq", "embed")

        def body(carry, pp):
            x = carry
            x, _, _ = apply_block("enc", x, pp, cfg, pos)
            return x, None

        x, _ = jax.lax.scan(body, x, p["enc"])
        return layernorm(x, p["enc_norm"]["w"], p["enc_norm"]["b"], cfg.norm_eps)

    def audio_cross_caches(p, enc_out):
        """Cross-attention K/V per decoder layer (train: the only cache)."""
        def per_layer(pp):
            ck, cv = cross_kv(enc_out, pp["b0"]["xattn"], cfg)
            return {"b0": {"cross_k": ck, "cross_v": cv}}
        return [jax.vmap(per_layer)(p["stacks"][0])]

    # --------------------------------------------------------------- inputs --

    def build_inputs(p, batch):
        """Returns (x, positions, targets, loss_mask, caches_for_train)."""
        if cfg.family == "vlm":
            patches = batch["patches"].astype(cdt)       # (B, Np, D)
            tokens = batch["tokens"]                     # (B, St+1)
            inp, tgt = tokens[:, :-1], tokens[:, 1:]
            xt = jnp.take(p["embed"], inp, axis=0).astype(cdt)
            x = jnp.concatenate([patches, xt], axis=1)
            B, S, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            Np = patches.shape[1]
            targets = jnp.concatenate(
                [jnp.zeros((B, Np), tgt.dtype), tgt], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros((B, Np), jnp.float32),
                 jnp.ones_like(tgt, jnp.float32)], axis=1)
            return (sh.constrain(x, "batch", "seq", "embed"), positions,
                    targets, mask, None)
        if cfg.family == "audio":
            tokens = batch["tokens"]
            inp, tgt = tokens[:, :-1], tokens[:, 1:]
            x = embed_tokens(p, inp)
            B, S, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            enc_out = encode_frames(p, batch["frames"])
            return x, positions, tgt, None, audio_cross_caches(p, enc_out)
        tokens = batch["tokens"]
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        x = embed_tokens(p, inp)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        return x, positions, tgt, None, None

    # ----------------------------------------------------------------- loss --

    def loss(p, batch):
        x, positions, targets, mask, train_caches = build_inputs(p, batch)
        x, _, aux = run_stacks(x, p["stacks"], positions, train_caches)
        aux = aux["router_loss"]
        logits = lm_logits(p, x)
        total = xent(logits, targets, mask) + cfg.router_aux_weight * aux
        if cfg.mtp:
            tokens = batch["tokens"]
            h = x[:, :-1]
            nxt = embed_tokens(p, tokens[:, 1:-1])
            m = p["mtp"]
            z = jnp.concatenate(
                [rmsnorm(h, m["norm"]["w"], cfg.norm_eps), nxt], axis=-1)
            z = z @ m["proj"].astype(cdt)
            B, S2, _ = z.shape
            pos2 = jnp.broadcast_to(jnp.arange(S2)[None, :], (B, S2))
            kind = "mla_dense" if cfg.use_mla else "dense"
            z, _, _ = apply_block(kind, z, m["block"], cfg, pos2)
            total = total + 0.3 * xent(lm_logits(p, z), tokens[:, 2:])
        return total, aux

    # -------------------------------------------------------------- serving --

    def init_caches(batch: int, s_max: int, filled: int = 0) -> Pytree:
        out = []
        for period, reps in groups:
            def one():
                c = {f"b{i}": init_cache(kind, cfg, batch, s_max,
                                         enc_seq=cfg.enc_seq)
                     for i, kind in enumerate(period)}
                if filled:
                    c = jax.tree.map(
                        lambda t: (jnp.full_like(t, filled)
                                   if t.dtype == jnp.int32 and t.ndim == 1
                                   else t), c)
                return c
            c = one()
            if reps > 1:
                c = jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (reps,) + t.shape), c)
            out.append(c)
        return out

    def cache_axes() -> Pytree:
        """Logical sharding axes mirroring init_caches' structure."""
        def _is_axes(x):
            return isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x)
        out = []
        for period, reps in groups:
            c = {f"b{i}": _block_cache_axes(kind, cfg)
                 for i, kind in enumerate(period)}
            if reps > 1:
                c = jax.tree.map(lambda t: ("layers",) + t, c, is_leaf=_is_axes)
            out.append(c)
        return out

    def prefill(p, batch):
        if cfg.family == "audio":
            tokens = batch["tokens"]
            enc_out = encode_frames(p, batch["frames"])
            x = embed_tokens(p, tokens)
            caches = audio_cross_caches(p, enc_out)
        elif cfg.family == "vlm":
            patches = batch["patches"].astype(cdt)
            tokens = batch["tokens"]
            xt = jnp.take(p["embed"], tokens, axis=0).astype(cdt)
            x = jnp.concatenate([patches, xt], axis=1)
            caches = None
        else:
            tokens = batch["tokens"]
            x = embed_tokens(p, tokens)
            caches = None
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x = sh.constrain(x, "batch", "seq", "embed")
        x, new_caches, _ = run_stacks(x, p["stacks"], positions, caches)
        return lm_logits(p, x[:, -1:]), new_caches

    def decode_step(p, tokens, caches, pos):
        """tokens: (B,1); pos: (B,) current position (== tokens generated).
        Returns (logits, caches), and where ``expert_slots`` the int32
        count of (token, held expert) rows the step's expert layers
        computed."""
        x = jnp.take(p["embed"], tokens, axis=0).astype(cdt)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if not cfg.rope_theta:
            x = x + _sinusoidal(pos[:, None], cfg.d_model).astype(cdt)
        x = sh.constrain(x, "batch", "seq", "embed")
        positions = pos[:, None]
        x, new_caches, aux = decode_stacks(x, p["stacks"], positions, caches)
        if expert_slots:
            return lm_logits(p, x), new_caches, aux["expert_rows"]
        return lm_logits(p, x), new_caches

    return Model(cfg=cfg, init=init, axes=axes, loss=loss, prefill=prefill,
                 decode_step=decode_step, init_caches=init_caches,
                 cache_axes=cache_axes, expert_slots=expert_slots,
                 inplace_layers=inplace_layers)


# ---------------------------------------------------------------------------
# parameter counting (MODEL_FLOPS for the roofline tables)
# ---------------------------------------------------------------------------


def count_params_from_specs(cfg: ModelConfig, active_only: bool = False) -> int:
    m = build_model(cfg)
    specs = jax.eval_shape(lambda: m.init(jax.random.key(0)))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(specs)[0]:
        n = 1
        for s in leaf.shape:
            n *= int(s)
        if active_only and cfg.n_experts and any(
            getattr(k, "key", None) == "experts" for k in path
        ):
            n = n * cfg.top_k // cfg.n_experts
        total += n
    return total
