"""Batched serving engine: one wave of requests at a time over prefill +
decode.

The engine takes up to ``max_batch`` requests as one wave, runs the model's
prefill over their prompts (left-padded to one length), installs its caches
into preallocated ones sized to ``max_seq`` (``model.init_caches``: KV
caches, SSM states, or both side by side in one tree) and steps every slot
of the wave together with one jitted decode step per token until the last
request is done.  A slot whose request has finished keeps decoding until
the wave ends; nothing is admitted between steps.
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

        A round is left-padded to one length, so every slot shares one
        decode position; the host keeps it as an integer (the prompt
        length plus the steps dispatched) for the ``max_seq`` stop, and
        the device keeps its own ``pos`` as the decode step's input.

        Host spans (``repro.spans``): ``serve.generate`` holds the call;
        in it ``serve.prefill`` runs to the first token's dispatch, then
        the decode loop alternates ``serve.emit`` (the step's tokens for
        every slot moved to the host in one transfer, then the appends and
        stop checks; ``syncs`` counts the waits, 1) and ``serve.dispatch``
        (one decode step, its argmax, the start of its tokens' copy to the
        host and the position's increment).  Where the model holds a share
        of its experts (``model.expert_slots``), the step's count of
        (token, held expert) rows is copied beside its tokens, and the emit
        after a decode step records it as ``expert_rows``, with
        ``expert_slots`` (expert layers × experts held).  ``serve.generate``
        records ``inplace_layers``: the layers whose caches each decode step
        updates in place inside their scanned group's stacked buffers."""
        assert len(requests) <= self.max_batch
        with span("serve.generate",
                  inplace_layers=self.model.inplace_layers):
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
            next_tok.copy_to_host_async()
        host_pos = plen
        live = np.ones((B,), bool)
        rows = None             # the step's expert rows, where counted
        max_new = max(r.max_new_tokens for r in requests)
        for _ in range(max_new):
            with span("serve.emit", syncs=1) as emit:
                toks = np.asarray(next_tok)     # the step's one wait
                if rows is not None:            # copied beside the tokens
                    emit.args.update(expert_rows=int(rows),
                                     expert_slots=self.model.expert_slots)
                for i, r in enumerate(requests):
                    if live[i]:
                        r.out.append(int(toks[i]))
                        if (self.eos_id is not None
                                and r.out[-1] == self.eos_id) \
                                or len(r.out) >= r.max_new_tokens:
                            live[i] = False
                            r.done = True
                stop = not live.any() or host_pos + 1 >= self.max_seq
            if stop:
                break
            with span("serve.dispatch"):
                logits, caches, *counted = self._decode(
                    self.params, next_tok[:, None], caches, pos)
                next_tok = jnp.argmax(logits[:, -1, :],
                                      axis=-1).astype(jnp.int32)
                next_tok.copy_to_host_async()
                if counted:
                    rows, = counted
                    rows.copy_to_host_async()
                pos = pos + 1
                host_pos += 1
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
