"""Step functions: training (loss + grad + AdamW update, optional microbatch
gradient accumulation) and serving (prefill / decode) — the functions the
launcher jits, shards, and the dry-run lowers."""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import sharding as sh
from repro.models.model import Model, build_model
from repro.optim import OptimizerConfig, apply_updates, init_opt_state

Pytree = Any


@dataclass(frozen=True)
class TrainState:
    params: Pytree
    opt: Pytree
    step: jax.Array


def make_train_step(model: Model, opt_cfg: OptimizerConfig,
                    microbatches: int = 1):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``microbatches > 1`` accumulates gradients over batch slices with a scan —
    the standard memory/overlap lever the §Perf tuner can move.
    """

    def loss_fn(params, batch):
        loss, aux = model.loss(params, batch)
        return loss, aux

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            def slice_mb(i, t):
                mb = t.shape[0] // microbatches
                return jax.lax.dynamic_slice_in_dim(t, i * mb, mb, axis=0)

            def acc_body(carry, i):
                gsum, lsum, asum = carry
                mb = jax.tree.map(functools.partial(slice_mb, i), batch)
                (l, a), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                return (jax.tree.map(jnp.add, gsum, g), lsum + l, asum + a), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum, asum), _ = jax.lax.scan(
                acc_body, (zeros, jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32)),
                jnp.arange(microbatches))
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss, aux = lsum / microbatches, asum / microbatches

        params, opt_state, metrics = apply_updates(
            opt_cfg, params, grads, opt_state)
        metrics = dict(metrics, loss=loss, aux_loss=aux)
        return params, opt_state, metrics

    return train_step


def state_shardings(model: Model, opt_cfg: OptimizerConfig, pspecs: Pytree):
    """NamedShardings of ``(params, optimizer state)`` under the installed
    sharding rules.  Each param follows its logical axes (an axis whose mesh
    extent does not divide the dim replicates); each moment follows its
    param; factored second-moment stats and the step counter replicate."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.optim.adamw import OptState

    replicated = NamedSharding(sh.mesh(), PartitionSpec())
    pshard = jax.tree.map(
        lambda axes, s: sh.named_sharding_for(s.shape, *axes),
        model.axes(), pspecs, is_leaf=_is_axes)
    ospecs = jax.eval_shape(functools.partial(init_opt_state, opt_cfg), pspecs)

    def mirror(psh, ps, o):
        if isinstance(o, jax.ShapeDtypeStruct) and o.shape == ps.shape:
            return psh
        return jax.tree.map(lambda _: replicated, o)

    return pshard, OptState(
        step=replicated,
        m=jax.tree.map(mirror, pshard, pspecs, ospecs.m),
        v=jax.tree.map(mirror, pshard, pspecs, ospecs.v))


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


def make_serve_steps(model: Model):
    def prefill_step(params, batch):
        return model.prefill(params, batch)

    def decode_step(params, tokens, caches, pos):
        logits, caches = model.decode_step(params, tokens, caches, pos)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return next_tok, logits, caches

    return prefill_step, decode_step


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs) per (arch × shape cell) — dry-run stand-ins
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train: the token batch (B, S+1) (+ stub modality inputs);
    prefill: prompt batch (B, S);
    decode: one new token against a KV/state cache of S (built separately).
    """
    B, S = cell.global_batch, cell.seq_len
    sp: dict[str, jax.ShapeDtypeStruct] = {}
    if cell.kind == "train":
        ntok = S + 1
        if cfg.family == "vlm":
            # patches replace leading positions: text tokens = S - patches
            sp["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.num_patches, cfg.d_model), jnp.dtype(cfg.dtype))
            ntok = S - cfg.num_patches + 1
        if cfg.family == "audio":
            sp["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype))
        sp["tokens"] = jax.ShapeDtypeStruct((B, ntok), jnp.int32)
        return sp
    if cell.kind == "prefill":
        ntok = S
        if cfg.family == "vlm":
            sp["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.num_patches, cfg.d_model), jnp.dtype(cfg.dtype))
            ntok = S - cfg.num_patches
        if cfg.family == "audio":
            sp["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), jnp.dtype(cfg.dtype))
        sp["tokens"] = jax.ShapeDtypeStruct((B, ntok), jnp.int32)
        return sp
    # decode: one token per sequence
    sp["tokens"] = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    sp["pos"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    return sp


def batch_axes(cfg: ModelConfig, cell: ShapeCell) -> dict:
    """Logical sharding axes for the input batch dict."""
    if cell.kind == "decode":
        return {"tokens": ("batch", None), "pos": ("batch",)}
    a: dict[str, tuple] = {"tokens": ("batch", None)}
    if cfg.family == "vlm":
        a["patches"] = ("batch", None, None)
    if cfg.family == "audio":
        a["frames"] = ("batch", None, None)
    return a
