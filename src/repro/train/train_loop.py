"""The training loop: data prefetch → jitted step → watchdog → async
checkpoints, with restart-from-commit (fault tolerance) built in.

Small enough to read, complete enough to run the e2e example
(examples/train_lm.py trains a ~100M-param config for a few hundred steps on
this container) and structured the way a pod-scale launcher drives it.
"""

from __future__ import annotations

import functools
import pathlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.data.pipeline import DataConfig, Prefetcher, host_batch
from repro.models import sharding as sh
from repro.models.model import build_model
from repro.optim import OptimizerConfig, apply_updates, init_opt_state
from repro.train import checkpoint as ckpt
from repro.train.fault_tolerance import (FailureInjector, StragglerWatchdog,
                                         SimulatedFailure)
from repro.train.steps import make_train_step, state_shardings


@dataclass
class LoopConfig:
    ckpt_dir: str               # a committed checkpoint here is resumed
    total_steps: int = 200
    log_every: int = 10
    ckpt_every: int = 50
    seed: int = 0
    microbatches: int = 1
    keep_ckpts: int = 3


@dataclass
class LoopResult:
    last_step: int
    losses: list = field(default_factory=list)
    straggler_flags: list = field(default_factory=list)
    restored_from: int | None = None
    # bytes of params + optimizer state each device holds, in jax.devices()
    # order (a mesh shards them; without one device 0 holds everything)
    device_state_bytes: list = field(default_factory=list)


def train(cfg: ModelConfig, opt_cfg: OptimizerConfig, loop: LoopConfig,
          data_cfg: DataConfig | None = None,
          injector: FailureInjector | None = None,
          mesh=None, rules=None) -> LoopResult:
    """Run (or resume) training.  Restores from the latest committed
    checkpoint in ``loop.ckpt_dir`` if one exists."""
    model = build_model(cfg)
    data_cfg = data_cfg or DataConfig(
        vocab_size=cfg.vocab_size, seq_len=256, global_batch=8,
        seed=loop.seed)
    step_fn = make_train_step(model, opt_cfg, microbatches=loop.microbatches)

    with sh.scope(mesh, rules) if mesh is not None else _nullcontext():
        key = jax.random.key(loop.seed)

        def init_state(key):
            params = model.init(key)
            return params, init_opt_state(opt_cfg, params)

        if mesh is None:
            shardings = None
            jitted = jax.jit(step_fn, donate_argnums=(0, 1))
        else:
            # params and moments are created already sharded: built on one
            # device and resharded, they would not fit it at full width
            shardings = state_shardings(
                model, opt_cfg, jax.eval_shape(model.init, key))
            jitted = jax.jit(
                step_fn, donate_argnums=(0, 1),
                out_shardings=shardings + (NamedSharding(mesh, P()),))
        params, opt_state = jax.jit(init_state, out_shardings=shardings)(key)
        start_step = 0
        restored = None
        latest = ckpt.latest_step(loop.ckpt_dir)
        if latest is not None:
            state = ckpt.restore(loop.ckpt_dir, latest, (params, opt_state),
                                 shardings=shardings)
            params, opt_state = state
            start_step = latest
            restored = latest

        saver = ckpt.AsyncCheckpointer(loop.ckpt_dir, keep=loop.keep_ckpts)
        watchdog = StragglerWatchdog()
        result = LoopResult(last_step=start_step, restored_from=restored,
                            device_state_bytes=_device_bytes(
                                (params, opt_state)))

        prefetch = Prefetcher(data_cfg, start_step=start_step)
        try:
            for step in range(start_step, loop.total_steps):
                got_step, batch_np = prefetch.next()
                assert got_step == step, (got_step, step)
                batch = {"tokens": jax.numpy.asarray(batch_np)}
                _extend_batch(batch, cfg, data_cfg, step)
                t0 = time.perf_counter()
                if injector is not None:
                    injector.maybe_fail(step)
                params, opt_state, metrics = jitted(params, opt_state, batch)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                if watchdog.observe(step, dt):
                    result.straggler_flags.append(step)
                if step % loop.log_every == 0 or step == loop.total_steps - 1:
                    result.losses.append((step, loss))
                next_step = step + 1
                if next_step % loop.ckpt_every == 0:
                    saver.save(next_step, (params, opt_state))
                result.last_step = next_step
            saver.save(loop.total_steps, (params, opt_state))
            saver.wait()
        finally:
            prefetch.close()
        return result


def _device_bytes(tree) -> list[int]:
    per = {d: 0 for d in jax.devices()}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device] += shard.data.nbytes
    return list(per.values())


def _extend_batch(batch, cfg, data_cfg, step):
    """Stub modality inputs for vlm/audio families (deterministic)."""
    import jax.numpy as jnp

    B = batch["tokens"].shape[0]
    if cfg.family == "vlm":
        rng = np.random.default_rng([data_cfg.seed, step, 7])
        batch["patches"] = jnp.asarray(
            rng.standard_normal((B, cfg.num_patches, cfg.d_model), np.float32))
    if cfg.family == "audio":
        rng = np.random.default_rng([data_cfg.seed, step, 9])
        batch["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.enc_seq, cfg.d_model), np.float32))


class _nullcontext:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
