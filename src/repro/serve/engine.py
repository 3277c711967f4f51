"""Batched serving engine: continuous-batching-lite over prefill + decode.

The engine owns preallocated KV/state caches (``model.init_caches``) sized to
``max_seq``, admits requests up to ``max_batch``, runs one jitted prefill per
admission wave (left-padded into the shared cache) and steps all live
sequences together with one jitted decode per token.  Slot recycling on EOS
mimics continuous batching at the granularity this container can exercise.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.model import Model, build_model
from repro.spans import span


@dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 16
    out: list[int] = field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, max_batch: int = 4,
                 max_seq: int = 512, eos_id: int | None = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(2,))

    def generate(self, requests: list[Request]) -> list[Request]:
        """Greedy-decode a wave of requests (all admitted together).

        Host spans (``repro.spans``): ``serve.generate`` holds the call;
        in it ``serve.prefill`` runs to the first token's dispatch, then
        the decode loop alternates ``serve.emit`` (the live slots' tokens
        pulled to the host, the stop checks and the position pulled;
        ``syncs`` counts those pulls) and ``serve.dispatch`` (one decode
        step, its argmax and the position's increment)."""
        assert len(requests) <= self.max_batch
        with span("serve.generate"):
            self._generate(requests)
        return requests

    def _generate(self, requests: list[Request]) -> None:
        B = len(requests)
        # uniform-length prefill via right-align padding to the longest prompt
        plen = max(len(r.prompt) for r in requests)
        with span("serve.prefill"):
            toks = np.zeros((B, plen), np.int32)
            for i, r in enumerate(requests):
                toks[i, plen - len(r.prompt):] = r.prompt     # left-pad with 0
            batch = {"tokens": jnp.asarray(toks)}
            if self.cfg.family == "vlm":
                batch["patches"] = jnp.zeros(
                    (B, self.cfg.num_patches, self.cfg.d_model), jnp.float32)
            if self.cfg.family == "audio":
                batch["frames"] = jnp.zeros(
                    (B, self.cfg.enc_seq, self.cfg.d_model), jnp.float32)

            logits, pre_caches = self.model.prefill(self.params, batch)
            caches = self.model.init_caches(B, self.max_seq, filled=plen)
            caches = _install_prefix(caches, pre_caches, self.max_seq)

            pos = jnp.full((B,), plen, jnp.int32)
            next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        live = np.ones((B,), bool)
        max_new = max(r.max_new_tokens for r in requests)
        for _ in range(max_new):
            with span("serve.emit") as emit:
                syncs = 0
                for i, r in enumerate(requests):
                    if live[i]:
                        r.out.append(int(next_tok[i]))
                        syncs += 1
                        if (self.eos_id is not None
                                and r.out[-1] == self.eos_id) \
                                or len(r.out) >= r.max_new_tokens:
                            live[i] = False
                            r.done = True
                stop = not live.any()
                if not stop:
                    syncs += 1
                    stop = int(pos[0]) + 1 >= self.max_seq
                emit.args["syncs"] = syncs
            if stop:
                break
            with span("serve.dispatch"):
                logits, caches = self._decode(
                    self.params, next_tok[:, None], caches, pos)
                next_tok = jnp.argmax(logits[:, -1, :],
                                      axis=-1).astype(jnp.int32)
                pos = pos + 1
        for r in requests:
            r.done = True


def _install_prefix(caches, pre_caches, max_seq):
    """Copy prefill caches (length = prompt) into the preallocated max_seq
    caches, padding the sequence dim.

    Every leaf must either match the preallocated shape exactly or pad up to
    it.  An unmergeable leaf (rank/dtype mismatch, or a prefill dim *larger*
    than the preallocation) is a hard error: silently keeping the
    preallocated leaf would leave the KV cache zeroed and decode would read
    an empty context with no signal that anything went wrong.
    """
    def merge(dst, src):
        if dst.shape == src.shape:
            return src
        if dst.ndim == src.ndim and dst.dtype == src.dtype:
            # pad src's differing (sequence) dims up to dst
            pads = []
            ok = True
            for a, b in zip(src.shape, dst.shape):
                if a > b:
                    ok = False
                pads.append((0, b - a))
            if ok:
                return jnp.pad(src, pads).astype(dst.dtype)
        raise ValueError(
            f"_install_prefix: cannot merge prefill cache leaf "
            f"{src.shape}/{src.dtype} into preallocated {dst.shape}/"
            f"{dst.dtype} (max_seq={max_seq}) — decode would silently read "
            f"a zeroed cache; check init_caches/prefill cache layouts match")

    # (length counters already match: init_caches(filled=plen) == prefill's)
    return jax.tree.map(merge, caches, pre_caches)
