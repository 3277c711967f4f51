"""Flash attention (forward) Pallas kernel — the prefill hot-spot.

IO-aware blocked attention (FlashAttention-style) adapted to the TPU memory
hierarchy: KV blocks stream HBM→VMEM, the running (m, l, acc) state lives in
VMEM scratch, and the (block_q × block_kv) score tile is sized for the MXU.
``block_q``/``block_kv`` are tile sizes in the paper's search space; the grid
order (batch·head, q, kv) with kv minor is the scratch-friendly schedule.

GQA is handled by folding the group into the q-head index map so KV blocks are
fetched once per group.  Causal masking skips fully-masked KV blocks via the
grid (cheap revisit in interpret mode; Mosaic elides the compute).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale, causal, block_q, block_kv, q_offset, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    # f32 inputs contract at f32 precision: Mosaic's default for f32
    # operands is one bf16 pass, ~1e-2 off an f32 reference
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else None)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale         # (bq, d)
    k = k_ref[0].astype(jnp.float32)                 # (bkv, d)
    s = jnp.dot(q, k.T, precision=prec,
                preferred_element_type=jnp.float32)           # (bq, bkv)

    # ``kv_len`` is the true (unpadded) KV length; when the KV axis was
    # padded to a block multiple the tail columns must never win the softmax
    kv_padded = kv_len % block_kv != 0
    if causal or kv_padded:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0) + q_offset
        kpos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        valid = kpos < kv_len if kv_padded else True
        if causal:
            valid = (kpos <= qpos) & valid
        s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, precision=prec, preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,          # (B, Hq, Sq, D)
    k: jnp.ndarray,          # (B, Hkv, Skv, D)
    v: jnp.ndarray,          # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    block_q: int = 512,
    block_kv: int = 512,
    scale: float | None = None,
    interpret: bool,
) -> jnp.ndarray:
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    # Non-divisible block sizes: pad both sequence axes up to a block
    # multiple.  Padded query rows are sliced off the output; padded key
    # columns are masked to NEG_INF inside the kernel (``kv_len``).
    sq_p = -(-Sq // bq) * bq
    skv_p = -(-Skv // bkv) * bkv

    qr = q.reshape(B * Hq, Sq, D)
    kr = k.reshape(B * Hkv, Skv, D)
    vr = v.reshape(B * Hkv, Skv, D)
    if sq_p != Sq:
        qr = jnp.pad(qr, ((0, 0), (0, sq_p - Sq), (0, 0)))
    if skv_p != Skv:
        kr = jnp.pad(kr, ((0, 0), (0, skv_p - Skv), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, skv_p - Skv), (0, 0)))

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal,
        block_q=bq, block_kv=bkv, q_offset=Skv - Sq, kv_len=Skv,
    )
    out = pl.pallas_call(
        kern,
        grid=(B * Hq, sq_p // bq, skv_p // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda h, i, j, _g=group: (h // _g, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda h, i, j, _g=group: (h // _g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out[:, :Sq].reshape(B, Hq, Sq, D)
