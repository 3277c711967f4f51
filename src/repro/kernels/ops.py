"""Jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples and carries the default block
configurations.  ``interpret`` is always the caller's choice: ``False``
compiles with Mosaic and needs a TPU (off a TPU Pallas refuses it), ``True``
runs the Pallas interpreter on any backend."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as _attention
from . import covariance as _covariance
from . import matmul as _matmul
from . import ref
from . import ssd as _ssd
from . import syr2k as _syr2k


def _pad2(x: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def matmul(x, y, block_m: int = 256, block_n: int = 256, block_k: int = 512,
           *, interpret: bool):
    m, n = x.shape[0], y.shape[1]
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, x.shape[1]))
    xp = _pad2(x, bm, bk)
    yp = _pad2(y, bk, bn)
    out = _matmul.matmul(xp, yp, block_m=bm, block_n=bn, block_k=bk,
                         interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "block_k",
                                             "interpret"))
def syr2k(a, b, block_i: int = 256, block_j: int = 256, block_k: int = 512,
          *, interpret: bool):
    n = a.shape[0]
    bi, bj, bk = min(block_i, n), min(block_j, n), min(block_k, a.shape[1])
    ap = _pad2(a, max(bi, bj), bk)
    bp = _pad2(b, max(bi, bj), bk)
    out = _syr2k.syr2k(ap, bp, block_i=bi, block_j=bj, block_k=bk,
                       interpret=interpret)
    return out[:n, :n]


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "block_k",
                                             "interpret"))
def covariance(data, block_i: int = 256, block_j: int = 256,
               block_k: int = 512, *, interpret: bool):
    m = data.shape[1]
    bi, bj, bk = min(block_i, m), min(block_j, m), min(block_k, data.shape[0])
    dp = _pad2(data, bk, max(bi, bj))
    out = _covariance.covariance(dp, block_i=bi, block_j=bj, block_k=bk,
                                 interpret=interpret)
    return out[:m, :m]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 512, block_kv: int = 512, *,
                    interpret: bool):
    return _attention.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, chunk: int = 64, *, interpret: bool):
    return _ssd.ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=interpret)
