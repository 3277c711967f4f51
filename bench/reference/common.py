"""What the plain references share: the benchmark's weights from a seed,
RMSNorm, matrix products at the reference's precision or the control's,
and the output head read at chosen positions."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn


def normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def outer_key(key):
    """Key of the embedding, final norm and head."""
    return jax.random.fold_in(key, 0)


def layer_key(key, i):
    """Key of layer ``i`` (a Python or traced integer)."""
    return jax.random.fold_in(jax.random.fold_in(key, 1), i)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def to_fp8(a):
    """Round to float8 e4m3 under one scale for the whole tensor, and back."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / float(jnp.finfo(FP8).max)
    return (a / s).astype(FP8).astype(jnp.float32) * s


def matmul(mode: str):
    """``x @ w`` in float32 at full precision ("f32"), or with both
    operands first rounded to float8 ("fp8": the control)."""
    if mode == "f32":
        return lambda x, w: jnp.matmul(x, w, precision=HI)
    if mode == "fp8":
        return lambda x, w: jnp.matmul(to_fp8(x), to_fp8(w), precision=HI)
    raise ValueError(f"unknown precision mode {mode!r}")


@jax.jit
def _gather(x, read):
    return jnp.take_along_axis(x, read[..., None], axis=1)


def head_logits(x, read, norm_w, head, eps, mode):
    """Final norm and head at positions ``read`` (n, k) of ``x`` (n, S, D)."""
    h = rmsnorm(_gather(x, read), norm_w, eps)
    return jax.jit(matmul(mode))(h, head)
